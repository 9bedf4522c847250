#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serve path starts on the chip.

One process, one command, from the repo root:

    python chip_smoke.py                               # one TPU chip
    python chip_smoke.py --replicas 4                  # four one-chip replicas
    python chip_smoke.py --mesh model=2 --replicas 2   # 2 x (TP=2, kv-sharded)
    python chip_smoke.py --kernels                     # every Pallas kernel x shape
    python chip_smoke.py --state-space                 # recurrent state + scan, tiny preset
    python chip_smoke.py --latent                      # latent (MLA) pool + its kernel, tiny preset
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal # tiny preset, CPU

It drives the path a user runs — ``llm_np_cp_tpu.cli serve`` with CLI
defaults (``--sample-epilogue auto``, greedy, bf16
weights and cache): write a seeded random Qwen2.5-1.5B checkpoint in the
HF layout (28 layers, hidden 1536, vocab 151,936 — full published width
and depth), load it, place it, warm up (= compile), answer 16 requests
over HTTP from the stock client, drain on SIGTERM — and then checks, by
the repo's own means, that nothing was quietly downgraded on the way:
platform is ``tpu``, the tick resolved to unified / Pallas ragged
attention / fused epilogue, every request finished ``length``/``stop``
with the token count asked for, repeats and stream-vs-non-stream agree
(identical tokens, or a first divergence the reference forward itself
ranks as a near-tie — bf16 rounding depends on the packed width),
``restarts == 0``, ``decode_impl_degraded == 0``, no compile after
warm-up, every device the topology names holds its share of weights and
pool, and the server's first token has — under a plain jitted
``models.forward`` of the same weights (XLA attention, no kernels, no
cache) — a logit within tolerance of that forward's maximum.

It reports counts and set-up times only; tok/s, utilization and roofline
shares are the benchmark's job.  Without ``--rehearsal`` a run that finds
no TPU exits non-zero naming the platform it found and prints no result.
The last stdout line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One process holds the chip: the server runs in the main thread (so the
real SIGTERM drain is exercised), the HTTP client in a worker thread that
never touches a JAX op, and the reference forward after the server has
drained and released its buffers.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import faulthandler
import gc
import io
import json
import logging
import os
import re
import shutil
import signal
import sys
import tempfile
import threading
import time
import traceback
from importlib import metadata
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

# the whole run must end inside the driver's 1200 s, whatever hangs
WATCHDOG_S = 1150

# |reference max logit - reference logit of the server's token| allowed,
# as a share of the reference's (max - mean) logit spread.  bf16 weights
# through 28 layers move a logit by ~1% of that spread between two
# correct implementations; a kernel that computes garbage picks a token
# ~100% of the spread below the maximum.
LOGIT_GAP_TOLERANCE = 0.05

# the checkpoint and the prompts are functions of this seed
SEED = 0

# per request, cold compile of a straggler included
REQUEST_TIMEOUT_S = 600.0

# bytes a device may hold beyond a peer without a name for them: probe
# inputs and per-tick operands land on the default device
DEFAULT_DEVICE_SLACK = 64 << 20


class Report:
    """Phase times, facts and failed checks, in the order they happened."""

    def __init__(self) -> None:
        self.phases: dict[str, float] = {}
        self.facts: dict = {}
        self.failures: list[str] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = round(time.perf_counter() - t0, 2)
            say(f"phase {name}: {self.phases[name]:.2f} s")

    def check(self, ok: bool, what: str) -> None:
        say(("ok   " if ok else "FAIL ") + what)
        if not ok:
            self.failures.append(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Tee(io.TextIOBase):
    """stdout that also keeps what the server printed (its banner is the
    resolution report the smoke checks) and when the engine-built line
    appeared (the boundary between load+place and warm-up)."""

    ENGINE_BUILT = "unified tick ACTIVE"

    def __init__(self, out) -> None:
        self.out = out
        self.kept: list[str] = []
        self.engine_built_at: float | None = None

    def write(self, s: str) -> int:
        self.kept.append(s)
        if self.engine_built_at is None and self.ENGINE_BUILT in s:
            self.engine_built_at = time.perf_counter()
        return self.out.write(s)

    def flush(self) -> None:
        self.out.flush()

    def text(self) -> str:
        return "".join(self.kept)


# ----------------------------------------------------------------------
# presets
# ----------------------------------------------------------------------

def presets(rehearsal: bool):
    from llm_np_cp_tpu.config import QWEN_2_5_1_5B, tiny_config

    if rehearsal:
        return dict(
            config=tiny_config("qwen2"), dtype="f32", slots=4,
            prompt_len=32, max_tokens=8, block_size=8,
            prompt_lens=[4, 7, 12, 16, 24, 32], max_tokens_mix=(4, 8),
        )
    return dict(
        config=QWEN_2_5_1_5B, dtype="bf16", slots=8, prompt_len=512,
        max_tokens=64, block_size=64,
        prompt_lens=[32, 48, 64, 100, 128, 200, 256, 300, 384, 448, 512, 512],
        max_tokens_mix=(32, 64),
    )


def build_requests(p: dict) -> list[dict]:
    """12 distinct token-id prompts of mixed length, half streamed, then
    four repeats: one prompt sent twice the same way, one streamed prompt
    re-sent non-streamed, one non-streamed prompt re-sent streamed, and
    the longest prompt again."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    vocab = p["config"].vocab_size
    lo, hi = p["max_tokens_mix"]
    reqs = []
    for i, n in enumerate(p["prompt_lens"]):
        reqs.append(dict(
            name=f"p{i}", prompt=[int(t) for t in rng.integers(1, vocab, n)],
            max_tokens=hi if i % 2 else lo, stream=i % 2 == 0, seed=i,
        ))
    k = len(reqs)

    def again(i: int, stream: bool) -> dict:
        return dict(reqs[i], name=f"p{i}-again", stream=stream)

    reqs += [again(1, reqs[1]["stream"]), again(2, not reqs[2]["stream"]),
             again(3, not reqs[3]["stream"]),
             again(k - 1, reqs[k - 1]["stream"])]
    return reqs


# ----------------------------------------------------------------------
# the client side (worker thread; HTTP only, never a JAX op)
# ----------------------------------------------------------------------

def drive_requests(host: str, port: int, model: str,
                   reqs: list[dict]) -> list[dict]:
    from llm_np_cp_tpu.serve.http.client import (
        astream_completion,
        post_completion,
    )

    async def one(i: int, r: dict) -> dict:
        # staggered arrivals: later prompts prefill while earlier rows
        # decode, so the tick sees both kinds of row together
        await asyncio.sleep(0.05 * i)
        body = {"model": model, "prompt": r["prompt"],
                "max_tokens": r["max_tokens"], "seed": r["seed"]}
        if r["stream"]:
            out = await astream_completion(host, port, body,
                                           timeout=REQUEST_TIMEOUT_S)
            return dict(status=out["status"], tokens=out["token_ids"],
                        finish=out["finish_reason"], error=out.get("error"))
        status, resp = await asyncio.get_running_loop().run_in_executor(
            None, post_completion, host, port, body, REQUEST_TIMEOUT_S)
        choice = (resp.get("choices") or [{}])[0]
        return dict(status=status, tokens=choice.get("token_ids", []),
                    finish=choice.get("finish_reason"),
                    error=resp.get("error"))

    async def all_() -> list[dict]:
        return await asyncio.gather(*(one(i, r) for i, r in enumerate(reqs)))

    return asyncio.run(all_())


def scrape_sum(text: str, name: str) -> float | None:
    """Sum of a gauge/counter over its label sets (one per replica)."""
    vals = re.findall(rf"^llm_serve_{name}(?:\{{[^}}]*\}})? (\S+)$", text, re.M)
    return sum(float(v) for v in vals) if vals else None


def device_memory() -> list[dict] | None:
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats()
        if st is None:
            return None
        out.append(dict(id=d.id, bytes_in_use=int(st["bytes_in_use"]),
                        peak_bytes_in_use=int(st["peak_bytes_in_use"])))
    return out


def first_divergence(a: list[int], b: list[int]) -> int | None:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def client_main(rep: Report, model: str, port_file: str,
                server_done: threading.Event, shared: dict) -> None:
    """Everything between 'server is listening' and 'SIGTERM'."""
    from llm_np_cp_tpu.serve.http.client import http_get
    from tools.compile_counter import CompileCounter

    try:
        while not os.path.exists(port_file):
            if server_done.wait(0.1):
                return  # the server died during start-up; main reports it
        host, port_s = open(port_file).read().split()
        port = int(port_s)
        shared["listening_at"] = time.perf_counter()
        counter = CompileCounter()
        with counter.watch():
            shared["memory"] = device_memory()
            status, raw = http_get(host, port, "/healthz")
            health = json.loads(raw)
            rep.check(status == 200 and health.get("status") == "ok",
                      f"/healthz before traffic: {status} {health.get('status')}")
            reqs = shared["requests"]
            with rep.phase("requests"):
                results = drive_requests(host, port, model, reqs)
            shared["results"] = results
            status, raw = http_get(host, port, "/healthz")
            shared["health"] = json.loads(raw)
            _, raw = http_get(host, port, "/metrics")
            shared["metrics"] = raw.decode()
        shared["compiles_after_warmup"] = list(counter.events)
    except Exception as e:  # noqa: BLE001 — reported by main, which fails
        shared["client_error"] = f"{type(e).__name__}: {e}"
    finally:
        shared["sigterm_at"] = time.perf_counter()
        if not server_done.is_set():
            os.kill(os.getpid(), signal.SIGTERM)


# ----------------------------------------------------------------------
# checks over what came back
# ----------------------------------------------------------------------

def check_resolution(rep: Report, args, banner: str) -> None:
    """The banner is the engine's own report of what it resolved to."""
    tp = args.tp
    m = re.search(r"unified tick ACTIVE.*\(ragged attention: (\w+), "
                  r"epilogue=(\w+)\)", banner)
    line = re.search(r"^\[serve\] model=.*$", banner, re.M)
    rep.facts["banner"] = line.group(0) if line else None
    tick = "unified" if m else "split"
    ragged, epilogue = (m.group(1), m.group(2)) if m else (None, None)
    topo = re.search(r"topo=(.*?), prefix_cache", line.group(0)) if line else None
    rep.facts["resolution"] = dict(
        tick=tick, ragged_attn_impl=ragged, epilogue_impl=epilogue,
        topology=topo.group(1) if topo else None,
    )
    # a model-sharded mesh keeps the XLA logits tail by design (the
    # epilogue kernel streams the full lm head)
    want_epilogue = "xla" if tp > 1 else "fused"
    rep.check(tick == "unified", f"tick resolved to {tick}, want unified")
    rep.check(ragged == "pallas",
              f"ragged attention resolved to {ragged}, want pallas")
    rep.check(epilogue == want_epilogue,
              f"sampling epilogue resolved to {epilogue}, want {want_epilogue}")
    if tp > 1:
        rep.check("kv-sharded" in (rep.facts["resolution"]["topology"] or ""),
                  "pool is kv-sharded over the model axis")


def check_results(rep: Report, reqs: list[dict], results: list[dict]) -> None:
    bad = []
    for r, out in zip(reqs, results):
        ok = (out["status"] == 200 and out["finish"] in ("length", "stop")
              and (len(out["tokens"]) == r["max_tokens"]
                   or out["finish"] == "stop"))
        if not ok:
            bad.append(f"{r['name']}: status={out['status']} "
                       f"finish={out['finish']} tokens={len(out['tokens'])}/"
                       f"{r['max_tokens']} error={out['error']}")
    rep.check(not bad, f"{len(reqs) - len(bad)} of {len(reqs)} requests "
              "finished length/stop with the token count asked for"
              + ("; " + "; ".join(bad) if bad else ""))


class Reference:
    """The plain jitted ``models.forward`` of the same checkpoint (XLA
    attention, no kernels, no cache), outside any timing.  Every
    sequence is right-padded to one length — causal attention makes the
    logits at a position independent of what follows it — so the whole
    smoke costs ONE compile of it."""

    def __init__(self, p: dict, ckpt: str) -> None:
        import jax
        import jax.numpy as jnp
        from jax import lax

        from llm_np_cp_tpu.models.transformer import final_logits, forward
        from llm_np_cp_tpu.utils.loading import load_model

        dtype = jnp.bfloat16 if p["dtype"] == "bf16" else jnp.float32
        _, self.params, config = load_model(ckpt, dtype=dtype, tokenizer=False)
        self.length = p["prompt_len"] + p["max_tokens"]

        @jax.jit
        def logits_at(params, ids, pos):
            x, _ = forward(params, ids, config, skip_logits=True)
            row = lax.dynamic_slice_in_dim(x, pos, 1, axis=1)
            return final_logits(params, row, config)[0, 0]

        self._logits_at = logits_at

    def next_logits(self, ids: list[int]):
        """f32 logits for the token that follows ``ids``."""
        import jax.numpy as jnp
        import numpy as np

        padded = list(ids) + [0] * (self.length - len(ids))
        out = self._logits_at(self.params, jnp.asarray([padded], jnp.int32),
                              jnp.int32(len(ids) - 1))
        return np.asarray(out, np.float32)

    def gaps(self, ids: list[int], tokens: list[int]) -> dict:
        """How far below the reference maximum each of ``tokens`` sits
        as the continuation of ``ids``, against the stated tolerance."""
        import numpy as np

        ref = self.next_logits(ids)
        spread = float(ref.max() - ref.mean())
        return dict(
            gaps=[round(float(ref.max() - ref[t]), 5) for t in tokens],
            argmax=int(ref.argmax()), spread=round(spread, 5),
            tolerance=round(LOGIT_GAP_TOLERANCE * spread, 5),
            finite=bool(np.isfinite(ref).all()),
        )


def same_or_near_tie(ref: Reference, prompt: list[int], a: list[int],
                     b: list[int]) -> tuple[bool, str]:
    """Two greedy streams of one prompt: identical — or diverging only
    where the reference forward itself has a near-tie.  On the chip the
    packed width changes with what else is in the tick, bf16 matmuls
    round differently per width, and with random weights the top two of
    151,936 logits are often within a few bf16 ulps (measured: a 0.044
    gap flipped at token 63 of 64 between two runs).  Cross-request
    contamination or a stale cache diverges at a token the reference
    does NOT rank at the top."""
    div = first_divergence(a, b)
    if div is None:
        return True, "identical"
    if div >= min(len(a), len(b)):
        return False, f"lengths differ ({len(a)} vs {len(b)})"
    g = ref.gaps(prompt + a[:div], [a[div], b[div]])
    ok = g["finite"] and max(g["gaps"]) <= g["tolerance"]
    return ok, (f"first divergence at token {div}: {a[div]} vs {b[div]} sit "
                f"{g['gaps'][0]:.4f} and {g['gaps'][1]:.4f} below the "
                f"reference max (tolerance {g['tolerance']:.4f}) — "
                + ("a reference near-tie" if ok else "NOT a near-tie"))


def check_repeats(rep: Report, ref: Reference, reqs: list[dict],
                  results: list[dict]) -> None:
    by_name = {r["name"]: (r, out) for r, out in zip(reqs, results)}
    rep.facts["repeats"] = {}
    for r, out in zip(reqs, results):
        if not r["name"].endswith("-again"):
            continue
        first_req, first = by_name[r["name"].removesuffix("-again")]
        kind = ("repeat" if first_req["stream"] == r["stream"]
                else "streamed vs non-streamed")
        ok, how = same_or_near_tie(ref, r["prompt"], first["tokens"],
                                   out["tokens"])
        rep.facts["repeats"][r["name"]] = how
        rep.check(ok, f"{r['name']} ({kind}): {how}")


def check_memory(rep: Report, args, p: dict, banner: str) -> None:
    mem = rep.facts.get("memory_after_warmup")
    if mem is None:
        say("memory_stats() unavailable on this backend; placement not checked")
        return
    import math

    import jax

    from llm_np_cp_tpu.models.transformer import param_shapes

    cfg = p["config"]
    itemsize = 2 if p["dtype"] == "bf16" else 4
    param_bytes = itemsize * sum(
        math.prod(shape) for shape in jax.tree.leaves(
            param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    pool = re.search(r"pool=(\d+)x(\d+)", banner)
    nb, bs = (int(pool.group(1)), int(pool.group(2))) if pool else (0, 0)
    pool_bytes = (2 * cfg.num_hidden_layers * nb * bs
                  * cfg.num_key_value_heads * cfg.head_dim * itemsize)
    share = (param_bytes + pool_bytes) // args.tp
    named = args.tp * args.replicas
    rep.facts["expected_bytes_per_device"] = share
    for d in mem[:named]:
        rep.check(d["bytes_in_use"] >= 0.85 * share,
                  f"device {d['id']} holds its share of weights + pool: "
                  f"{d['bytes_in_use'] / 2**20:.0f} MiB in use, peak "
                  f"{d['peak_bytes_in_use'] / 2**20:.0f} MiB "
                  f"(share {share / 2**20:.0f} MiB)")
    for d in mem[named:]:
        say(f"device {d['id']} (not named by the topology): "
            f"{d['bytes_in_use'] / 2**20:.0f} MiB in use")
    if named > 1:
        others = max(d["peak_bytes_in_use"] for d in mem[1:named])
        extra = mem[0]["peak_bytes_in_use"] - others
        rep.check(extra <= DEFAULT_DEVICE_SLACK,
                  f"device 0 peak exceeds its peers' by {extra / 2**20:.0f} "
                  f"MiB (allowed: {DEFAULT_DEVICE_SLACK >> 20} MiB of probe "
                  "inputs and per-tick operands on the default device)")


def check_numerics(rep: Report, ref: Reference, p: dict, reqs: list[dict],
                   results: list[dict]) -> None:
    """The server's first token under the reference forward, for the
    shortest prompt (one prefill chunk) and the longest (several chunks
    through the pool): both ends of the ragged kernel's range."""
    order = sorted(range(len(p["prompt_lens"])),
                   key=lambda i: p["prompt_lens"][i])
    rep.facts["numerics"] = []
    for i in (order[0], order[-1]):
        if not results[i]["tokens"]:
            rep.check(False, f"numerics {reqs[i]['name']}: no token to check")
            continue
        tok = results[i]["tokens"][0]
        g = ref.gaps(reqs[i]["prompt"], [tok])
        fact = dict(prompt=reqs[i]["name"], prompt_len=len(reqs[i]["prompt"]),
                    server_token=tok, reference_argmax=g["argmax"],
                    gap=g["gaps"][0], spread=g["spread"],
                    tolerance=g["tolerance"])
        rep.facts["numerics"].append(fact)
        rep.check(g["finite"] and fact["gap"] <= fact["tolerance"],
                  f"numerics {fact['prompt']} (len {fact['prompt_len']}): "
                  f"server token {tok} sits {fact['gap']:.4f} below the "
                  f"reference forward's max logit (argmax {g['argmax']}; "
                  f"tolerance {fact['tolerance']:.4f} = "
                  f"{LOGIT_GAP_TOLERANCE:.0%} of spread {g['spread']:.3f})")


def check_reference(rep: Report, ref: Reference, args, reqs, results) -> None:
    """Token streams against an earlier run's report (the one-chip run):
    identical or near-tie divergence is the gate for replicas; under a
    TP mesh agreement and first divergence are reported, not gated
    (sharding changes the bf16 reduction order by design)."""
    with open(args.reference) as f:
        theirs = json.load(f)["tokens"]
    same, notes, bad = 0, [], []
    for r, out in zip(reqs, results):
        ok, how = same_or_near_tie(ref, r["prompt"], theirs[r["name"]],
                                   out["tokens"])
        same += how == "identical"
        if how != "identical":
            notes.append(f"{r['name']}: {how}")
        if not ok:
            bad.append(r["name"])
    rep.facts["reference"] = dict(path=args.reference, identical=same,
                                  of=len(reqs), divergences=notes)
    msg = (f"{same} of {len(reqs)} token streams identical to "
           f"{args.reference}" + ("; " + "; ".join(notes) if notes else ""))
    if args.tp > 1:
        say("info " + msg + " (reported, not gated, under a TP mesh)")
    else:
        rep.check(not bad, msg)


# ----------------------------------------------------------------------
# modes
# ----------------------------------------------------------------------

def run_kernels(rep: Report, rehearsal: bool) -> None:
    from llm_np_cp_tpu.ops.pallas import support

    with rep.phase("kernel matrix"):
        # a rehearsal runs the probe shapes through the interpreter: it
        # proves the cases and their XLA twins, not Mosaic
        verdicts = (support.kernel_matrix(support.PROBE_SHAPES, interpret=True)
                    if rehearsal else support.kernel_matrix())
    rep.facts["kernels"] = verdicts
    for v in verdicts:
        bs = "" if v["block_size"] is None else f" bs={v['block_size']}"
        rep.check(v["ok"], f"{v['kernel']} @ {v['shape']}{bs}: "
                  + (f"max |kernel - XLA| = {v['max_err']:.4g}"
                     if "max_err" in v else v["error"]))


def run_serve(rep: Report, args) -> None:
    import llm_np_cp_tpu.cli as cli
    from llm_np_cp_tpu.native.build import build as build_native
    from llm_np_cp_tpu.utils import synthetic

    p = presets(args.rehearsal)
    cfg = p["config"]
    rep.facts["model"] = dict(
        name="tiny qwen2 (rehearsal)" if args.rehearsal else "Qwen2.5-1.5B",
        layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
        vocab=cfg.vocab_size, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        dtype=p["dtype"],
    )
    say(f"model: {rep.facts['model']}")

    # the binary's name carries a hash of its source + flags, so one
    # found on disk IS this source built this way; otherwise it is built
    lib = build_native()
    rep.facts["native_reader"] = lib.name if lib else None
    say("checkpoint reader: " + (f"native C++ ({lib.name}, keyed on "
        "safetensors_reader.cc + flags)" if lib
        else "python safetensors (native build unavailable, see log)"))

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    ckpt = os.path.join(workdir, "ckpt")
    port_file = os.path.join(workdir, "port")
    shared: dict = {"requests": build_requests(p)}
    server_done = threading.Event()
    tee = Tee(sys.stdout)
    try:
        with rep.phase("checkpoint write"):
            import ml_dtypes
            import numpy as np

            nbytes = synthetic.write_random_checkpoint(
                ckpt, cfg, seed=SEED,
                dtype=ml_dtypes.bfloat16 if p["dtype"] == "bf16" else np.float32)
            synthetic.write_id_tokenizer(ckpt, cfg.vocab_size)
        say(f"checkpoint: {nbytes / 1e9:.2f} GB under {workdir} (seed {SEED})")

        argv = ["serve", "--model", ckpt, "--port", "0", "--port-file",
                port_file, "--slots", str(p["slots"]),
                "--prompt-len", str(p["prompt_len"]),
                "--max-tokens", str(p["max_tokens"]),
                "--block-size", str(p["block_size"]),
                "--dtype", p["dtype"], "--cache-dtype", p["dtype"],
                "--sampler", "greedy"]
        if args.mesh:
            argv += ["--mesh", args.mesh]
        if args.replicas > 1:
            argv += ["--replicas", str(args.replicas)]
        say("server argv: " + " ".join(argv))
        client = threading.Thread(
            target=client_main, name="chip-smoke-client", daemon=True,
            args=(rep, ckpt, port_file, server_done, shared))
        t_start = time.perf_counter()
        client.start()
        sys.stdout = tee
        try:
            cli.run(argv, default_model=ckpt)
        except BaseException as e:  # noqa: BLE001 — SystemExit included
            traceback.print_exc(file=sys.__stderr__)
            rep.check(False, f"server exited with {type(e).__name__}: {e}")
        finally:
            sys.stdout = tee.out
            server_done.set()
            t_end = time.perf_counter()
        client.join(timeout=30)
        banner = tee.text()

        if "listening_at" in shared:
            # the CLI prints its tick line once the (first) engine is
            # built — weights loaded and placed, pool allocated, probes
            # run — and the port file appears after warm-up
            built = tee.engine_built_at or shared["listening_at"]
            rep.phases["load+place"] = round(built - t_start, 2)
            rep.phases["warm-up (compile)"] = round(
                shared["listening_at"] - built, 2)
            rep.phases["drain"] = round(t_end - shared["sigterm_at"], 2)
            for k in ("load+place", "warm-up (compile)", "drain"):
                say(f"phase {k}: {rep.phases[k]:.2f} s")
        rep.check("listening_at" in shared, "server reached 'listening'")
        rep.check("client_error" not in shared,
                  "client finished" + (f": {shared.get('client_error')}"
                                       if "client_error" in shared else ""))
        check_resolution(rep, args, banner)
        rep.check("[serve] drained, bye" in banner,
                  "server drained and exited on SIGTERM")
        if "results" not in shared:
            return
        reqs, results = shared["requests"], shared["results"]
        rep.facts["tokens"] = {r["name"]: out["tokens"]
                               for r, out in zip(reqs, results)}
        check_results(rep, reqs, results)
        health = shared.get("health", {})
        rep.facts["healthz"] = {k: health.get(k) for k in
                                ("status", "restarts", "mesh")}
        rep.check(health.get("status") == "ok" and health.get("restarts") == 0,
                  f"/healthz after traffic: status={health.get('status')} "
                  f"restarts={health.get('restarts')}")
        metrics = shared.get("metrics", "")
        for name in ("decode_impl_degraded", "restarts_total"):
            val = scrape_sum(metrics, name)
            rep.facts[name] = val
            rep.check(val == 0, f"scrape {name} = {val}")
        aborted = scrape_sum(metrics, "requests_aborted_total")
        rep.facts["requests_aborted_total"] = aborted
        rep.check(not aborted, f"scrape requests_aborted_total = {aborted}")
        compiles = shared.get("compiles_after_warmup", [])
        rep.facts["compiles_after_warmup"] = len(compiles)
        rep.check(not compiles, f"{len(compiles)} compile events after "
                  "warm-up" + (f": {sorted(set(compiles))}" if compiles else ""))
        rep.facts["memory_after_warmup"] = shared.get("memory")
        check_memory(rep, args, p, banner)
        gc.collect()  # the drained engines' buffers, before a second load
        with rep.phase("reference checks (load + plain forward)"):
            ref = Reference(p, ckpt)
            check_repeats(rep, ref, reqs, results)
            if args.reference:
                check_reference(rep, ref, args, reqs, results)
            check_numerics(rep, ref, p, reqs, results)
    finally:
        sys.stdout = tee.out
        shutil.rmtree(workdir, ignore_errors=True)


# the tiny presets ``run_tiny_preset`` serves: flag -> (model type, what
# the report calls it)
TINY_PRESETS = {"state_space": ("falcon_h1", "state-space"),
                "latent": ("deepseek_v3", "latent")}


def run_tiny_preset(rep: Report, rehearsal: bool, which: str) -> None:
    """A tiny preset through ``ServeEngine`` directly for a few ticks - a
    prompt of several prefill chunks beside decode rows, a slot reused -
    and every served token under ``models.forward`` of the same weights:
    a one-minute check for whoever changes what the preset has and no
    dense stack does.  ``state_space``: ``falcon_h1`` (a Mamba-2 mixer
    beside attention in every layer: the recurrent state and the scan,
    ops/ssm.py).  ``latent``: ``deepseek_v3`` (latent attention over a pool
    of one row a token, ops/pallas/latent_attention.py; shared experts
    beside sigmoid-routed ones)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.models import forward, init_params
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine

    model_type, name = TINY_PRESETS[which]
    cfg = tiny_config(model_type)
    dtype = jnp.float32 if rehearsal else jnp.bfloat16
    params = init_params(jax.random.PRNGKey(SEED), cfg, dtype=dtype)
    with rep.phase(f"{name}: engine build + serve"):
        engine = ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
            num_blocks=32, block_size=8, max_seq_len=96, prefill_chunk=8,
            cache_dtype=dtype)
        rng = np.random.default_rng(SEED)
        reqs = [engine.submit(rng.integers(1, cfg.vocab_size, n).tolist(),
                              max_new_tokens=12, seed=i)
                for i, n in enumerate((5, 37, 11))]  # the third reuses a slot
        engine.run_until_complete()
    rep.facts[which] = dict(
        tick="unified" if engine.mixed else "split",
        ragged_attn=engine.ragged_attn_impl, epilogue=engine.epilogue_impl,
        dispatches=engine.n_dispatches, requests=[])
    rep.check(engine.mixed and engine.ragged_attn_impl == "pallas"
              and engine.epilogue_impl == "fused",
              f"{name} stack served by the unified tick "
              f"(ragged attention {engine.ragged_attn_impl}, epilogue "
              f"{engine.epilogue_impl})")
    pages = engine.pool.pages
    if which == "state_space":
        state = pages.state["ssm"]
        rep.check(state.dtype == jnp.float32 and float(jnp.abs(state).max()) > 0,
                  f"recurrent state {state.shape} {state.dtype.name} beside "
                  "the pool, written")
    else:
        rep.check(pages.latent and pages.v is None and engine.pool_carried
                  and float(jnp.abs(pages.k.astype(jnp.float32)).max()) > 0,
                  f"latent pool {pages.k.shape} {pages.k.dtype.name} (rows of "
                  f"{pages.head_dim} values, no V beside them), written in place")
    plain = jax.jit(lambda p, ids: forward(p, ids, cfg)[0][0])
    with rep.phase(f"{name}: plain forward"):
        for r in reqs:
            seq = list(r.prompt) + list(r.generated)
            ids = np.zeros((64,), np.int32)  # one shape; causal
            ids[:len(seq)] = seq
            logits = np.asarray(plain(params, ids[None]), np.float32)
            lo = len(r.prompt) - 1
            at = logits[lo:lo + len(r.generated)]
            top = at.max(-1)
            gap = (top - at[np.arange(len(r.generated)), r.generated]) / (
                top - at.mean(-1))
            rep.facts[which]["requests"].append(dict(
                prompt_len=len(r.prompt), tokens=list(map(int, r.generated)),
                worst_gap=float(gap.max())))
            rep.check(len(r.generated) == 12 and bool(np.isfinite(at).all())
                      and float(gap.max()) <= LOGIT_GAP_TOLERANCE,
                      f"{name} request (prompt {len(r.prompt)}): "
                      f"{len(r.generated)} tokens, the worst sits "
                      f"{float(gap.max()):.2%} of the spread below the plain "
                      f"forward's maximum (tolerance {LOGIT_GAP_TOLERANCE:.0%})")


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


class LogWatch(logging.Handler):
    """The package logs a WARNING whenever it falls back (a kernel gated
    off, a reader that could not be built); the smoke keeps them, and
    counts which reader read each shard."""

    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.warnings: list[str] = []
        self.shards = {"native C++": 0, "python safetensors": 0}

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if record.levelno >= logging.WARNING:
            self.warnings.append(msg)
            say(f"log {record.levelname}: {msg}")
        for reader in self.shards:
            if f"read through the {reader} reader" in msg:
                self.shards[reader] += 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny preset on whatever platform JAX finds (the "
                    "CPU, with JAX_PLATFORMS=cpu): proves the script, not "
                    "the chip")
    ap.add_argument("--mesh", default="",
                    help="serve --mesh (e.g. model=2)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve --replicas")
    ap.add_argument("--kernels", action="store_true",
                    help="compile+run every Pallas kernel at the probe and "
                    "family shapes instead of driving the server")
    ap.add_argument("--state-space", action="store_true",
                    help="serve the tiny falcon_h1 preset (a state-space "
                    "mixer beside attention) for a few ticks and compare "
                    "with models.forward instead of driving the server")
    ap.add_argument("--latent", action="store_true",
                    help="serve the tiny deepseek_v3 preset (latent attention "
                    "over a pool of one row a token, shared experts) for a "
                    "few ticks and compare with models.forward instead of "
                    "driving the server")
    ap.add_argument("--reference", default=None, metavar="REPORT.json",
                    help="an earlier run's report: compare token streams")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the JSON report here "
                    "(default: chiprun_out/chip_smoke[.<topology>].json)")
    args = ap.parse_args(argv)
    # the smoke needs no network: the checkpoint directory is local
    os.environ.setdefault("HF_HUB_OFFLINE", "1")

    from llm_np_cp_tpu.parallel.sharding import parse_mesh_spec
    from llm_np_cp_tpu.utils.runtime import CACHE_ENV, configure_compile_cache

    args.tp = parse_mesh_spec(args.mesh).model if args.mesh else 1
    cache_dir = configure_compile_cache()
    import jax

    if args.rehearsal:
        # a rehearsal of --mesh/--replicas needs virtual devices
        jax.config.update("jax_num_cpu_devices", 8)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: no TPU — JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); run it through the chip tool, or pass "
              "--rehearsal for the tiny CPU preset", file=sys.stderr)
        return 2

    rep = Report()

    def version(pkg: str) -> str | None:
        with contextlib.suppress(metadata.PackageNotFoundError):
            return metadata.version(pkg)
        return None

    rep.facts.update(
        device=device, rehearsal=args.rehearsal,
        versions={p: version(p) for p in ("jax", "jaxlib", "libtpu")},
        compile_cache=dict(
            dir=cache_dir,
            source=CACHE_ENV if os.environ.get(CACHE_ENV) else "<checkout>/.jax_cache",
            entries_before=cache_entries(cache_dir)),
        topology=dict(mesh=args.mesh or None, replicas=args.replicas),
    )
    say(f"platform: {dev.platform}, device_kind: {dev.device_kind}, "
        f"devices: {len(devices)}, rehearsal: {str(args.rehearsal).lower()}")
    say(f"versions: {rep.facts['versions']}")
    say(f"compile cache: {cache_dir} ({rep.facts['compile_cache']['source']}, "
        f"{rep.facts['compile_cache']['entries_before']} entries)")

    watch = LogWatch()
    pkg_log = logging.getLogger("llm_np_cp_tpu")
    level = pkg_log.level
    pkg_log.addHandler(watch)
    pkg_log.setLevel(logging.INFO)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True,
                                      file=sys.__stderr__)
    tiny = next((k for k in TINY_PRESETS if getattr(args, k)), None)
    try:
        if args.kernels:
            run_kernels(rep, args.rehearsal)
        elif tiny:
            run_tiny_preset(rep, args.rehearsal, tiny)
        else:
            run_serve(rep, args)
    finally:
        pkg_log.removeHandler(watch)
        pkg_log.setLevel(level)
        faulthandler.cancel_dump_traceback_later()
    rep.facts["log_warnings"] = watch.warnings
    if not args.kernels and not tiny:
        rep.facts["shards_read_by"] = watch.shards
        say(f"shards read by: {watch.shards}")
    rep.check(not watch.warnings, f"{len(watch.warnings)} fallback warnings "
              "logged by the package")
    rep.facts["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    rep.facts.update(phases=rep.phases, failures=rep.failures,
                     ok=not rep.failures)

    topo = "kernels" if args.kernels else TINY_PRESETS[tiny][1] if tiny else "-".join(
        filter(None, [args.mesh.replace("=", ""),
                      f"replicas{args.replicas}" if args.replicas > 1 else ""]))
    out = Path(args.report) if args.report else (
        REPO / "chiprun_out"
        / f"chip_smoke{'.' + topo if topo else ''}"
          f"{'.rehearsal' if args.rehearsal else ''}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rep.facts, indent=1))
    say(f"report: {out}")
    say("phases: " + ", ".join(f"{k} {v:.2f} s" for k, v in rep.phases.items()))
    say(f"compile cache entries: {rep.facts['compile_cache']['entries_before']}"
        f" -> {rep.facts['compile_cache']['entries_after']} in {cache_dir}")
    if rep.failures:
        print(f"chip_smoke: FAILED {len(rep.failures)} check(s):",
              file=sys.stderr)
        for f in rep.failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    final = {"ok": True, "device": device}
    if args.rehearsal:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
