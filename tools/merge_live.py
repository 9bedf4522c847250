"""Merge captured bench output into a live-capture artifact, with provenance.

Usage:
    python tools/merge_live.py ARTIFACT.json SOURCE [SOURCE ...]

Each SOURCE is a file containing bench.py output (stdout summary lines
and/or raw child JSON lines — ``bench-phase`` noise is ignored).  The
LAST parseable JSON line of each source wins.  Merge rules:

- a summary line (has ``detail``): every ok=true config row replaces/adds
  into the artifact's ``detail``; the ``kernels``/``quality``/``warm``
  child blocks ride along the same way (VERDICT r4 weak #5: the durable
  artifact of record was assembled from three places — now one file
  carries perf + kernel verdicts + quality).
- a raw child line (has ``config``): merged directly under its name.

The headline ``value``/``vs_baseline`` are recomputed from the merged
``llama1b_bs8`` row.  Every merge appends a provenance record under
``detail.merge_provenance`` (ADVICE r4: a hand-merged artifact must say
which rows came from which retry window) listing source file, merged
row names, and the artifact's own mtime at merge.

If ARTIFACT.json does not exist, it is created from the first source's
summary line.
"""

from __future__ import annotations

import json
import os
import sys
import time

NORTH_STAR_TOK_S = 1000.0


def last_json(path: str) -> dict | None:
    out = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                out = json.loads(line)
            except json.JSONDecodeError:
                continue
    return out


# children whose FAILURES are evidence too: merged even with ok=false
_EVIDENCE_CHILDREN = ("kernels", "quality", "warm", "probe", "decomp")


def merge_one(live: dict, new: dict) -> list[str]:
    merged: list[str] = []
    if "detail" in new:  # a full summary line
        for name, row in new["detail"].items():
            if not isinstance(row, dict):
                continue
            # perf rows need ok=true (a failed retry must not overwrite a
            # captured number); evidence children merge regardless so
            # failures stay visible
            if row.get("ok") or name in _EVIDENCE_CHILDREN:
                live.setdefault("detail", {})[name] = row
                merged.append(name)
    elif "config" in new:  # a raw child line (e.g. `--run kernels` output)
        name = new["config"]
        if new.get("ok") or name in _EVIDENCE_CHILDREN:
            live.setdefault("detail", {})[name] = new
            merged.append(name)
    return merged


def main() -> None:
    if len(sys.argv) < 3:
        raise SystemExit(__doc__)
    artifact, sources = sys.argv[1], sys.argv[2:]
    live: dict = {}
    if os.path.exists(artifact):
        with open(artifact) as f:
            live = json.load(f)
    provenance = []
    for path in sources:
        new = last_json(path)
        if new is None:
            print(f"{path}: no parseable JSON line, skipped")
            continue
        if not live and "detail" in new:
            live = new  # first SUMMARY source seeds a fresh artifact wholesale
            # provenance lists what merge_one WOULD have taken (ok rows +
            # evidence children), not every detail scalar
            merged = sorted(
                name for name, row in new["detail"].items()
                if isinstance(row, dict)
                and (row.get("ok") or name in _EVIDENCE_CHILDREN)
            )
        else:
            if not live:
                # first source is a raw child line: seed the summary
                # skeleton so the artifact keeps the shape readers expect
                live = {
                    "metric": "decode_tokens_per_sec_per_chip",
                    "value": 0.0,
                    "unit": "tokens/s/chip",
                    "vs_baseline": 0.0,
                    "detail": {},
                }
            merged = merge_one(live, new)
        provenance.append({
            "source": os.path.basename(path),
            "merged": merged,
            "merged_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        })
        print(f"{path}: merged {merged}")
    if not live:
        raise SystemExit("no parseable source; artifact not written")
    live.setdefault("detail", {}).setdefault("merge_provenance", []).extend(
        provenance
    )
    bs8 = live["detail"].get("llama1b_bs8", {})
    if bs8.get("decode_tok_s_chip"):
        live["value"] = bs8["decode_tok_s_chip"]
        live["vs_baseline"] = round(live["value"] / NORTH_STAR_TOK_S, 3)
    # a merged artifact that now has real rows should not carry a stale
    # backend-unreachable error banner (idempotent across repeated merges)
    if (
        live.get("error")
        and not live["error"].startswith("(superseded by merge)")
        and any(
            r.get("ok") for r in live["detail"].values() if isinstance(r, dict)
        )
    ):
        live["error"] = f"(superseded by merge) {live['error']}"
    with open(artifact, "w") as f:
        json.dump(live, f)
        f.write("\n")
    print("headline:", live.get("value"))


if __name__ == "__main__":
    main()
