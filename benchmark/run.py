#!/usr/bin/env python3
"""One run of one benchmark cell on the served path.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process.  It reads the cell from ``BENCHMARK.json``
(its configuration, traffic mix and chips) and from the data files those
names point at, makes the weights on the device from ``--seed``, starts
``llm_np_cp_tpu.cli serve`` unchanged in the main thread, lets a child
process (``loadgen.py``, stdlib only) ramp and then send the traffic for
``--seconds`` seconds, awaits every request, sends itself SIGTERM so the
server drains, checks correctness OUTSIDE the window and prints one JSON
object as the last line of stdout.

This file holds no cell, configuration, traffic or metric name: all of
those are files the names in ``BENCHMARK.json`` lead to (README.md).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearsal`` runs it on whatever JAX
finds (the CPU in the sandbox and in the tests): the line then says
``"rehearsal": true`` and carries no device metric.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

import devtrace  # noqa: E402
import stats  # noqa: E402
import traffic as traffic_mod  # noqa: E402

# the whole run must end inside the driver's 360 s (1200 s when it compiles)
WATCHDOG_S = 1150
# seconds between starting the load generator and the ramp's first send
LOADGEN_LEAD_S = 1.5
# the profiler window of a traced run: a few seconds in the middle
PROFILE_S = 3.0
# requests the plain forward checks, the longest prompt among them
REFERENCE_SAMPLES = 8
REFERENCE_BATCH = 4


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Tee(io.TextIOBase):
    """stdout that also keeps what the server printed: its banner is the
    engine's own report of what it resolved to."""

    def __init__(self, out) -> None:
        self.out = out
        self.kept: list[str] = []

    def write(self, s: str) -> int:
        self.kept.append(s)
        return self.out.write(s)

    def flush(self) -> None:
        self.out.flush()

    def text(self) -> str:
        return "".join(self.kept)


# ----------------------------------------------------------------------
# the cell, from data
# ----------------------------------------------------------------------

def load_spec(data_root: Path, workload: str) -> dict:
    with open(data_root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(data_root / entry["file"]) as f:
        config = json.load(f)
    bdir = data_root / bench["paths"][0]
    traffic_path = bdir / "traffic" / f"{cell['traffic']}.json"
    with open(bdir / "cells" / f"{workload}.json") as f:
        params = json.load(f)

    def wanted(kind: str) -> list[str]:
        return [m["name"] for m in bench[kind]
                if "workloads" not in m or workload in m["workloads"]]

    return dict(bench=bench, cell=cell, config=config, config_name=entry["name"],
                traffic=traffic_mod.load_traffic(str(traffic_path)),
                traffic_path=str(traffic_path), params=params, dir=bdir,
                end_to_end=wanted("end_to_end"), per_layer=wanted("per_layer"))


def load_reader(path: Path):
    """A metric's reader: ``<name>.py`` with ``read(run)``, or
    ``<name>.json`` for a counter delta, ratio or gauge off ``/metrics``."""
    # a metric's name may hold dots, so the suffix is appended, not swapped
    py, js = Path(f"{path}.py"), Path(f"{path}.json")
    if py.exists():
        spec = importlib.util.spec_from_file_location(
            "reader_" + re.sub(r"\W", "_", path.name), py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    if js.exists():
        with open(js) as f:
            rule = json.load(f)
        return lambda run: read_scrape_rule(rule, run)
    return None


def load_peaks(path: Path, device_kind: str) -> dict:
    """The one place a peak is read from.  A device that is not in the
    table is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise LookupError(f"device kind {device_kind!r} is not in {path.name} "
                          f"(have: {', '.join(sorted(table))})")
    return table[device_kind]


def read_scrape_rule(rule: dict, run: dict) -> float | None:
    rec, scale = run["client"], float(rule.get("scale", 1.0))
    if rule["op"] == "delta":
        v = stats.scrape_delta(rec, rule["counter"])
    elif rule["op"] == "delta_ratio":
        num = stats.scrape_delta(rec, rule["counter"])
        den = stats.scrape_delta(rec, rule["per"])
        v = None if num is None or not den else num / den
    elif rule["op"] == "gauge_at_end":
        text = rec["scrapes"].get("end", {}).get("/metrics", {}).get("text", "")
        v = stats.scrape_mean(text, rule["gauge"], rule.get("labels"))
    else:
        raise ValueError(f"unknown scrape rule {rule['op']!r}")
    return None if v is None else v * scale


def read_metrics(spec: dict, names: list[str], kind_dir: str, units: dict,
                 run: dict) -> dict:
    out = {}
    for name in names:
        reader = load_reader(spec["dir"] / kind_dir / name)
        if reader is None:
            raise SystemExit(f"run.py: metric {name!r} has no reader under "
                             f"{spec['dir'] / kind_dir}")
        value = reader(run)
        if value is not None:  # nothing to read: leave the metric out
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def serve_argv(spec: dict, port_file: str, trace_dir: Path | None) -> list[str]:
    """``cli serve`` with CLI defaults plus what the cell's data says:
    pool flags from the cell, lengths from the traffic mix's maxima,
    dtype / block size / topology from the configuration."""
    serve, params = spec["config"].get("serve", {}), spec["params"]
    p_max, m_max = traffic_mod.limits(spec["traffic"])
    argv = ["serve", "--model", spec["config_name"], "--port", "0",
            "--port-file", port_file, "--slots", str(params["slots"]),
            "--prompt-len", str(p_max), "--max-tokens", str(m_max),
            "--block-size", str(serve.get("block_size", 64)),
            "--dtype", serve.get("dtype", "bf16"),
            "--cache-dtype", serve.get("cache_dtype", "bf16"),
            "--sampler", "greedy"]
    if params.get("num_blocks"):
        argv += ["--num-blocks", str(params["num_blocks"])]
    if serve.get("mesh"):
        argv += ["--mesh", serve["mesh"]]
    if int(serve.get("replicas", 1)) > 1:
        argv += ["--replicas", str(serve["replicas"])]
    if trace_dir is not None:
        argv += ["--trace-out", str(trace_dir / "host_trace.json")]
    return argv + [str(x) for x in params.get("serve_flags", [])]


# ----------------------------------------------------------------------
# weights, from the seed
# ----------------------------------------------------------------------

def make_weights(config, seed: int, dtype, on_host: bool):
    """The model's parameters as a function of ``--seed``.

    One chip: ``init_params`` is ONE jitted program, so every leaf is
    born on the device in the dtype it is served in.  A mesh / replica
    placement wants host buffers (each engine places its own copy, as
    ``cli._load(on_host=True)`` does): those are one seeded block of
    normal(0, 0.02) values tiled into every matrix at a leaf-specific
    offset - cheap to make for 15 GB, and as good as independent draws
    for speed and for the comparison with the plain forward, which runs
    on the same tensors."""
    import jax

    from llm_np_cp_tpu.models import init_params

    if not on_host:
        params = init_params(jax.random.PRNGKey(seed), config, dtype=dtype)
        return jax.block_until_ready(params)
    import ml_dtypes
    import numpy as np

    from llm_np_cp_tpu.models.transformer import param_shapes

    np_dtype = ml_dtypes.bfloat16 if dtype == jax.numpy.bfloat16 else np.float32
    rng = np.random.default_rng(seed)
    block = (rng.standard_normal(1 << 22, np.float32) * 0.02).astype(np_dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name.startswith("ln_") or name == "final_norm":
            out.append(np.ones(shape, np_dtype))
        else:
            out.append(np.resize(np.roll(block, 7919 * (i + 1)), shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------------------
# the watcher thread: everything between "listening" and SIGTERM
# ----------------------------------------------------------------------

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


class CompileLog:
    """Wall time of every compile-ish monitoring event JAX reports."""

    MARKERS = ("compile", "lowering")

    def __init__(self) -> None:
        self.events: list[tuple[float, str]] = []

    def listener(self, event: str, *a, **kw) -> None:
        if any(m in event for m in self.MARKERS):
            self.events.append((time.time(), event))

    def inside(self, w0: float, w1: float) -> list[str]:
        return sorted({e for t, e in self.events if w0 <= t < w1})


def watcher(args, spec: dict, port_file: str, out_dir: Path,
            server_done: threading.Event, shared: dict) -> None:
    import jax

    child = None
    try:
        listening: list[str] = []
        while len(listening) != 2:  # "<host> <port>", once it is whole
            if server_done.wait(0.05):
                return  # the server died during start-up; main reports it
            if os.path.exists(port_file):
                with open(port_file) as f:
                    listening = f.read().split()
        host, port = listening
        shared["listening_at"] = time.time()
        shared["memory_after_warmup"] = device_memory()
        tr, p = spec["traffic"], spec["params"]
        t0 = time.time() + LOADGEN_LEAD_S
        record_path = out_dir / "client.json"
        cmd = [sys.executable, str(BENCH / "loadgen.py"), "--host", host,
               "--port", port, "--traffic", spec["traffic_path"],
               "--seed", str(args.seed), "--seconds", str(args.seconds),
               "--t0", repr(t0), "--vocab", str(spec["config"]["vocab_size"]),
               "--out", str(record_path)]
        cmd += (["--clients", str(p["clients"])] if tr["loop"] == "closed"
                else ["--rate", repr(float(p["rate_rps"]))])
        env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
        child = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                                 stderr=subprocess.PIPE, text=True)
        w0 = t0 + float(tr.get("ramp_s", 0.0))
        w1 = w0 + args.seconds
        shared["window"] = (w0, w1)
        if args.trace:
            mid = (w0 + w1) / 2
            length = min(PROFILE_S, args.seconds / 2)
            time.sleep(max(0.0, mid - length / 2 - time.time()))
            prof_dir = out_dir / "profile"
            shutil.rmtree(prof_dir, ignore_errors=True)
            p0 = time.time()
            # no Python tracer: it hooks every call of the tick thread
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
            time.sleep(length)
            p1 = time.time()
            jax.profiler.stop_trace()
            shared["profile"] = dict(dir=str(prof_dir), start=p0, stop=p1,
                                     stopped_at=time.time())
        time.sleep(max(0.0, w1 - time.time()))
        shared["memory_after_window"] = device_memory()
        _, err = child.communicate(timeout=WATCHDOG_S)
        shared["loadgen_rc"] = child.returncode
        if child.returncode != 0:
            shared["watcher_error"] = f"loadgen exited {child.returncode}: {err[-2000:]}"
        elif record_path.exists():
            with open(record_path) as f:
                shared["client"] = json.load(f)
    except Exception as e:  # noqa: BLE001 - reported by main, which fails the run
        shared["watcher_error"] = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if not server_done.is_set():
            os.kill(os.getpid(), signal.SIGTERM)


# ----------------------------------------------------------------------
# checks over what came back
# ----------------------------------------------------------------------

class Checks:
    def __init__(self) -> None:
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        say(("ok   " if ok else "FAIL ") + what)
        if not ok:
            self.failures.append(what)


def check_resolution(chk: Checks, banner: str, tp: int) -> dict:
    """The banner is the engine's own report of what it resolved to."""
    m = re.search(r"unified tick ACTIVE.*\(ragged attention: (\w+), "
                  r"epilogue=(\w+)\)", banner)
    line = re.search(r"^\[serve\] model=.*$", banner, re.M)
    tick = "unified" if m else "split"
    ragged, epilogue = (m.group(1), m.group(2)) if m else (None, None)
    topo = re.search(r"topo=(.*?), prefix_cache", line.group(0)) if line else None
    pool = re.search(r"pool=(\d+)x(\d+)", banner)
    # a model-sharded mesh keeps the XLA logits tail by design (the
    # epilogue kernel streams the full lm head)
    want = "xla" if tp > 1 else "fused"
    chk.check(tick == "unified", f"tick resolved to {tick}, want unified")
    chk.check(ragged == "pallas", f"ragged attention resolved to {ragged}, want pallas")
    chk.check(epilogue == want, f"sampling epilogue resolved to {epilogue}, want {want}")
    if tp > 1:
        chk.check("kv-sharded" in (topo.group(1) if topo else ""),
                  "pool is kv-sharded over the model axis")
    return dict(tick=tick, ragged_attn=ragged, epilogue=epilogue,
                topology=topo.group(1) if topo else None,
                banner=line.group(0) if line else None,
                pool_blocks=int(pool.group(1)) if pool else None)


def check_client(chk: Checks, rec: dict) -> tuple[int, int]:
    reqs = stats.measured(rec)
    bad = [r for r in reqs if not stats.good(r)]
    for r in bad[:5]:
        say(f"  failed request {r['idx']}: status={r['status']} finish={r['finish']} "
            f"tokens={len(r['tokens'])}/{r['max_tokens']} error={r['error']}")
    chk.check(bool(reqs), f"{len(reqs)} requests were due in the window")
    chk.check(not bad, f"{len(reqs) - len(bad)} of {len(reqs)} measured requests "
              "finished 'length' with exactly the tokens asked for")
    ramp_bad = [r for r in rec["requests"] if r not in reqs and not stats.good(r)]
    chk.check(not ramp_bad, f"{len(ramp_bad)} ramp requests failed")
    chk.check(not rec["table_wrapped"], "the request table outlasted the window")
    return len(reqs), len(bad)


def check_counters(chk: Checks, rec: dict) -> None:
    end = rec["scrapes"].get("end", {})
    text = end.get("/metrics", {}).get("text", "")
    for name in ("decode_impl_degraded", "restarts_total", "requests_aborted_total"):
        val = stats.scrape_sum(text, name)
        chk.check(val == 0, f"scrape {name} = {val}")
    try:
        health = json.loads(end.get("/healthz", {}).get("text") or "{}")
    except ValueError:
        health = {}
    chk.check(health.get("status") == "ok",
              f"/healthz at the window's end: {health.get('status')}")


def reference_samples(rec: dict, seed: int) -> list[dict]:
    """A seeded sample of served requests, the longest prompt included."""
    pool = [r for r in stats.measured(rec) if stats.good(r) and not r["turn"]]
    if not pool:
        return []
    longest = max(pool, key=lambda r: r["prompt_len"])
    rest = [r for r in pool if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:REFERENCE_SAMPLES - 1]


def check_reference(chk: Checks, spec: dict, rec: dict, params, config,
                    seed: int) -> list[dict]:
    from reference import LOGIT_GAP_LIMIT, LOGIT_GAP_TOLERANCE, Reference

    picked = reference_samples(rec, seed)
    chk.check(len(picked) >= min(REFERENCE_SAMPLES, len(stats.measured(rec))),
              f"{len(picked)} served requests sampled for the plain forward")
    if not picked:
        return []
    # prompts are rebuilt from the seed: the record keeps only lengths
    table = traffic_mod.request_table(
        spec["traffic"], seed, max(r["idx"] for r in picked) + 1, config.vocab_size)
    p_max, m_max = traffic_mod.limits(spec["traffic"])
    ref = Reference(params, config, length=p_max + m_max, batch=REFERENCE_BATCH)
    results = ref.check([(table[r["idx"]]["prompt"], r["tokens"]) for r in picked])
    for r, res in zip(picked, results):
        res["idx"] = r["idx"]
    worst = max(results, key=lambda x: x["worst_ratio"])
    n_tok = sum(x["tokens"] for x in results)
    exact = sum(x["exact_argmax"] for x in results)
    chk.check(all(x["ok"] for x in results),
              f"plain forward: {n_tok} served tokens of {len(results)} requests "
              f"(prompts {min(x['prompt_len'] for x in results)}-"
              f"{max(x['prompt_len'] for x in results)}), {exact} are its exact "
              f"argmax; the worst sits {worst['worst_gap']:.4f} below its maximum = "
              f"{worst['worst_ratio']:.2%} of the spread {worst['spread']:.3f} "
              f"(limit {LOGIT_GAP_LIMIT:.0%}); least share of a request's tokens "
              f"within {LOGIT_GAP_TOLERANCE:.0%}: "
              f"{min(x['near_tie_share'] for x in results):.2%}; mean gap "
              f"{sum(x['mean_ratio'] * x['tokens'] for x in results) / n_tok:.3%} "
              f"of the spread; worst request's p99 "
              f"{max(x['ratio_quantiles'][2] for x in results):.2%}")
    return results


# ----------------------------------------------------------------------
# the traced run: host spans + device trace
# ----------------------------------------------------------------------

def load_host_trace(path: Path, w0: float, w1: float) -> dict | None:
    """The recorder's tick events inside the window, on the wall clock."""
    if not path.exists():
        return None
    with open(path) as f:
        data = json.load(f)
    epoch = data.get("otherData", {}).get("wall_epoch")
    if epoch is None:
        return None
    ticks, phases = [], []
    for ev in data["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        start = epoch + ev["ts"] / 1e6
        if not w0 <= start < w1:
            continue
        if ev["name"] == "tick":
            ticks.append(dict(start=start, dur_s=ev["dur"] / 1e6,
                              args=ev.get("args", {})))
        elif ev.get("cat") == "phase":
            phases.append(dict(name=ev["name"], start=start, dur_s=ev["dur"] / 1e6))
    return dict(ticks=ticks, phases=phases)


def load_device_trace(shared: dict, host_trace: dict | None) -> dict | None:
    prof = shared.get("profile")
    if not prof:
        return None
    path = devtrace.find_xplane(prof["dir"])
    if path is None:
        return None
    trace = devtrace.read_xplane(path)
    reduced = devtrace.reduce(trace)
    if reduced is None:
        return None
    reduced["wall"] = [prof["start"], prof["stop"]]
    # the trace's clock against the wall clock: the program's tick
    # annotation is in the profile (trace clock) and ENDS where the
    # recorder's dispatch phase ends (wall clock) - it wraps only the
    # jitted call, at the end of a phase that first packs the operands
    ann = sorted(s + d for p in trace["planes"] if not p["name"].startswith("/device:")
                 for ln in p["lines"] for n, s, d in ln["events"]
                 if n == devtrace.TICK_ANNOTATION)
    named = []
    disp = sorted(p["start"] + p["dur_s"] for p in (host_trace or {}).get("phases", [])
                  if p["name"] == "mixed_dispatch"
                  and prof["start"] - 1.0 <= p["start"] <= prof["stop"] + 1.0)
    shift = devtrace.align(ann, [d * 1e9 for d in disp])
    if shift is not None:
        named = [(p["start"] * 1e9 + shift,
                  (p["start"] + p["dur_s"]) * 1e9 + shift, p["name"])
                 for p in host_trace["phases"]]
        w0, w1 = reduced["window_ns"]
        reduced["wall"] = [(w0 - shift) / 1e9, (w1 - shift) / 1e9]
    reduced["idle_gaps"] = devtrace.name_gaps(reduced["gaps_ns"], named)
    del reduced["gaps_ns"]
    return reduced


# ----------------------------------------------------------------------
# main
# ----------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on whatever platform JAX finds (the CPU, with "
                    "JAX_PLATFORMS=cpu): proves the harness, never a device number")
    ap.add_argument("--data-root", default=str(ROOT),
                    help="directory that holds BENCHMARK.json and its data "
                    "files (tests point this at a temporary copy)")
    ap.add_argument("--override", action="append", default=[], metavar="KEY=JSON",
                    help="sweeps only: replace one key of the cell's parameter "
                    "file for this run (never used by the driver)")
    args = ap.parse_args(argv)
    spec = load_spec(Path(args.data_root), args.workload)
    for item in args.override:
        key, _, value = item.partition("=")
        spec["params"][key] = json.loads(value)
    serve = spec["config"].get("serve", {})
    chips = int(spec["cell"]["chips"])

    from llm_np_cp_tpu.utils.runtime import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax
    import jax.numpy as jnp

    if args.rehearsal and os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_num_cpu_devices", 8)  # a mesh needs devices
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"run.py: no TPU - JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); no result", file=sys.stderr)
        return 2
    if len(devices) < chips and not args.rehearsal:
        print(f"run.py: the cell needs {chips} chips, JAX found {len(devices)}; "
              "no result", file=sys.stderr)
        return 2
    try:
        peaks = load_peaks(spec["dir"] / "peaks.json", dev.device_kind)
    except LookupError as e:
        if not args.rehearsal:
            print(f"run.py: {e}; no result", file=sys.stderr)
            return 2
        peaks = None

    import faulthandler

    import llm_np_cp_tpu.cli as cli
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.parallel.sharding import parse_mesh_spec

    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True, file=sys.__stderr__)
    out_dir = spec["dir"] / "out" / f"{args.workload}-{args.seed}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    phases: dict[str, float] = {"imports": time.time() - PROCESS_START}
    say(f"workload {args.workload} seed {args.seed} seconds {args.seconds:g} "
        f"trace {args.trace}" + (" REHEARSAL" if args.rehearsal else ""))
    say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"devices {len(devices)}, compile cache {cache_dir}")

    config = ModelConfig.from_hf_dict(spec["config"])
    dtype = jnp.bfloat16 if serve.get("dtype", "bf16") == "bf16" else jnp.float32
    mesh_spec, replicas = serve.get("mesh", ""), int(serve.get("replicas", 1))
    tp = parse_mesh_spec(mesh_spec).model if mesh_spec else 1
    on_host = bool(mesh_spec) or replicas > 1
    t = time.time()
    params = make_weights(config, args.seed, dtype, on_host)
    phases["weights"] = time.time() - t
    say(f"weights from seed {args.seed}: {phases['weights']:.2f} s "
        f"({'host' if on_host else 'device'})")
    # the one substitution: the program's loader hands out these weights
    cli._load = lambda a, on_host=False: (None, params, config)

    port_file = str(out_dir / "port")
    argv_serve = serve_argv(spec, port_file, out_dir if args.trace else None)
    say("server argv: " + " ".join(argv_serve))

    compiles = CompileLog()
    jax.monitoring.register_event_listener(compiles.listener)
    shared: dict = {}
    server_done = threading.Event()
    tee = Tee(sys.stdout)
    chk = Checks()
    thread = threading.Thread(
        target=watcher, name="bench-watcher", daemon=True,
        args=(args, spec, port_file, out_dir, server_done, shared))
    t_serve = time.time()
    thread.start()
    sys.stdout = tee
    try:
        cli.run(argv_serve, default_model=spec["config_name"])
    except BaseException as e:  # noqa: BLE001 - SystemExit included
        traceback.print_exc(file=sys.__stderr__)
        chk.check(False, f"server exited with {type(e).__name__}: {e}")
    finally:
        sys.stdout = tee.out
        server_done.set()
    thread.join(timeout=60)
    banner = tee.text()
    if "watcher_error" in shared:
        chk.check(False, shared["watcher_error"])
    rec = shared.get("client")
    if rec is None:
        print("run.py: the load generator left no record; no result",
              file=sys.stderr)
        return 1
    w0, w1 = rec["window"]
    phases["engine build + warm-up"] = shared["listening_at"] - t_serve
    phases["loadgen start + ramp"] = w0 - shared["listening_at"]
    phases["awaiting the tail"] = rec["finished_at"] - w1

    resolution = check_resolution(chk, banner, tp)
    chk.check("[serve] drained, bye" in banner, "server drained and exited on SIGTERM")
    attempted, failed = check_client(chk, rec)
    check_counters(chk, rec)
    inside = compiles.inside(w0, w1)
    chk.check(not inside, f"{len(inside)} compile events inside the window"
              + (f": {inside}" if inside else ""))
    memory = shared.get("memory_after_window")
    gc.collect()  # the drained engine's pool, before the plain forward
    t = time.time()
    reference = []
    try:
        ref_params = params
        if on_host:
            from llm_np_cp_tpu.parallel.sharding import MeshPlan, make_mesh, shard_params

            plan = MeshPlan(model=tp * replicas if tp > 1 else 1)
            ref_params = (shard_params(params, config, plan, make_mesh(plan))
                          if tp > 1 else jax.device_put(params, devices[0]))
        reference = check_reference(chk, spec, rec, ref_params, config, args.seed)
    except Exception as e:  # noqa: BLE001 - an unchecked run is not correct
        traceback.print_exc(file=sys.__stderr__)
        chk.check(False, f"plain forward failed: {type(e).__name__}: {e}")
    phases["reference check"] = time.time() - t

    run = dict(workload=args.workload, seed=args.seed, seconds=args.seconds,
               trace=args.trace, process_start=PROCESS_START, client=rec,
               config=spec["config"], params=spec["params"],
               traffic=spec["traffic"], peaks=peaks, tp=tp, replicas=replicas,
               host_trace=None, device_trace=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max((d["peak_bytes_in_use"] for d in memory),
                                       default=0) if memory else 0}
    breakdown = None
    if args.trace:
        run["host_trace"] = load_host_trace(out_dir / "host_trace.json", w0, w1)
        if not args.rehearsal or dev.platform == "tpu":
            run["device_trace"] = dt = load_device_trace(shared, run["host_trace"])
            if dt:
                device["busy_s"], device["window_s"] = dt["busy_s"], dt["window_s"]
                breakdown = dict(
                    device_ops=[[k, v] for k, v in list(dt["ops_s"].items())[:10]],
                    idle_gaps=dt["idle_gaps"])
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in spec["bench"][kind]}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(spec, spec[kind], "layers" if args.trace else "e2e",
                           units, run)

    # what the last line has no room for
    reqs = stats.measured(rec)
    detail = dict(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=args.rehearsal, phases=phases,
        setup_s=w0 - PROCESS_START, resolution=resolution,
        memory_after_warmup=shared.get("memory_after_warmup"),
        memory_after_window=memory, compiles_inside_window=inside,
        requests=dict(
            sent=len(rec["requests"]), measured=len(reqs), failed=failed,
            ttft_samples=len(stats.series(rec, stats.ttft_s)),
            tpot_samples=len(stats.series(rec, stats.tpot_s)),
            prompt_len=[stats.percentile([r["prompt_len"] for r in reqs], q)
                        for q in (0, 50, 95, 100)],
            max_tokens=[stats.percentile([r["max_tokens"] for r in reqs], q)
                        for q in (0, 50, 95, 100)],
            late_ms=[1e3 * (stats.percentile(stats.series(rec, stats.late_s), q) or 0)
                     for q in (50, 95, 100)],
            in_flight=[stats.in_flight(rec, w0), stats.in_flight(rec, (w0 + w1) / 2),
                       stats.in_flight(rec, w1)]),
        latencies=[[r["prompt_len"], r["max_tokens"], stats.ttft_s(r), stats.tpot_s(r)]
                   for r in reqs],
        reference=reference, failures=chk.failures, metrics=metrics,
        device=device, breakdown=breakdown)
    (spec["dir"] / "out" / f"{args.workload}-{args.seed}.json").write_text(
        json.dumps(detail, indent=1))
    say("phases: " + ", ".join(f"{k} {v:.2f} s" for k, v in phases.items()))
    say(f"requests: {detail['requests']}")
    if memory:
        say("memory peak per device: " + ", ".join(
            f"{d['id']}: {d['peak_bytes_in_use'] / 2**20:.0f} MiB" for d in memory))
    faulthandler.cancel_dump_traceback_later()
    if args.trace and not args.rehearsal and "busy_s" not in device:
        print("run.py: the traced run read no device operation; no result",
              file=sys.stderr)
        return 1
    if not args.trace:
        shutil.rmtree(out_dir, ignore_errors=True)
    result = dict(correct=not chk.failures, attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearsal:
        # a number from a CPU run is never written under a metric's name
        result.update(rehearsal=True, metrics={}, rehearsal_metrics=metrics)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
