#!/usr/bin/env python3
"""How close is a ``brumby`` stack to float32, offline and SERVED?  The plain
float32 reference (``reference_brumby.py``: the attention form, no feature
map, no state) ON THE CHIP at the configuration's widths, against the program
on the same seeded weights - the question ``correct`` cannot ask (it ranks the
served tokens under the program's own bf16 ``models.forward``).

    python benchmark/parity_brumby.py --workload <cell> --seed <n>

makes the weights from the seed as ``run.py`` does and compares LOGITS, never
tokens, in three parts:

(a) ``forward``: ``models.forward`` (the state form over whole sequences, by
    chunks) over ``--forward-len`` seeded tokens;
(b) ``served``: a ``ServeEngine`` with the cell's block size, chunking and
    dtypes and ``--slots`` slots serves ``slots + slots // 4`` requests with
    prompts drawn over the traffic mix's range and ``--new`` answer tokens each
    (one of them the mix's longest answer): prefill through
    ``retention_packed``'s chunk passes beside decode rows, then decode through
    the state-update kernel, and - because there are more requests than slots -
    requests that START IN A SLOT ANOTHER HAS LEFT, whose state nothing
    cleared.  The logits every served token was drawn from are kept (the XLA
    tail, wrapped with a callback) for ``--samples`` requests, half of them
    from the second wave, and compared with the reference's full forward over
    prompt + the served tokens;
(c) ``long``: one request whose prompt is ``--long`` tokens (past the 8,320
    tokens of K/V a slot's state is the size of), its last ``--new`` positions.

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum
  (``reference.py``'s gap, measured against float32).

The run FAILS (exit 1) when any part's ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``.  Each ``--control`` serves part (b) again with ONE thing of
the PROGRAM changed, reports the same numbers and fails the same way - which
is what it is for: ``bf16_state`` (both leaves of the state kept in bf16),
``no_normaliser`` (the numerator alone), ``state_zeroed`` (every tick starts
every row's state from zero), ``fresh_ignored`` (a new request reads what its
slot's last request left).

A builder's diagnostic: not a metric, not part of ``correct``; writes
``benchmark/out/<cell>-<seed>.parity.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

# Limits on ``off`` (share of the float32 logits' spread).  Each lies between
# two readings on the chip at the published widths, 8 slots, 10 requests of
# prompts 64-256 compared over 48-640 tokens each (PERF.md section 6, PR 56):
# what the program read over its seeds (served: mean 0.0251, p99 0.0440;
# ``models.forward`` 0.0257 / 0.0465; a context of 8,496 tokens 0.0261 /
# 0.0403), and what the nearest broken program read (the state's two leaves
# kept in bf16: mean 0.0327, p99 0.0617; the normaliser dropped: 1.39 / 1.67).
# A bf16 program against float32 is rounding: it moves every logit a little; a
# wrong equation moves them by a share of the spread.  What these limits do NOT
# see with seeded weights: ``fresh_ignored`` (a gate of about one half forgets
# a slot's last request within a dozen tokens, and a prompt has 64 or more:
# the CPU tests, with prompts of 4-12 tokens, see it).
OFF_MEAN_LIMIT = 0.029
OFF_P99_LIMIT = 0.054

CONTROLS = ("bf16_state", "no_normaliser", "state_zeroed", "fresh_ignored")
# the controls the limits are there to tell from the program
MUST_FAIL = ("bf16_state", "no_normaliser", "state_zeroed")


def summary(x) -> dict:
    import numpy as np

    return dict(mean=float(np.mean(x)), p99=float(np.quantile(x, 0.99)),
                worst=float(np.max(x)))


def slow_packed(normalise: bool):
    """``ops/retention.retention_packed``'s contract, token by token over the
    packed axis on each token's own row of the state, with the normaliser
    left out: the control no argument of the real one expresses."""
    import jax.numpy as jnp
    from jax import lax

    from llm_np_cp_tpu.ops.pallas import retention_state_update as rsu

    def packed(s, z, layer, q, k, v, log_g, *, tok_row, start, count, fresh,
               **_):
        t, nh = q.shape[:2]
        hk, d_v = k.shape[1], v.shape[-1]
        f32 = jnp.float32
        zero = jnp.int32(0)
        hi = lax.Precision.HIGHEST

        def token(i, carry):
            s, z, o = carry
            row = tok_row[i]
            at = i - start[row]
            live = (at >= 0) & (at < count[row])
            where = (layer, row, zero, zero, zero)
            s_r = lax.dynamic_slice(s, where, (1, 1) + s.shape[2:])[0, 0]
            z_r = lax.dynamic_slice(z, where, (1, 1) + z.shape[2:])[0, 0]
            new = fresh[row] & (at == 0)
            s_r = jnp.where(new, 0.0, s_r.astype(f32))
            z_r = jnp.where(new, 0.0, z_r.astype(f32))
            g = jnp.exp(log_g[i].astype(f32))
            k_i, v_i = k[i].astype(f32), v[i].astype(f32)
            q_i = q[i].astype(f32).reshape(hk, nh // hk, -1)
            s1 = s_r * g[:, None, None] + rsu.phi(k_i)[:, :, None] * v_i[:, None, :]
            z1 = z_r * g[:, None, None] + k_i[:, :, None] * k_i[:, None, :]
            num = jnp.einsum("hgr,hrv->hgv", rsu.phi(q_i), s1, precision=hi)
            den = jnp.einsum("hga,hab,hgb->hg", q_i, z1, q_i, precision=hi)
            o_i = (num / jnp.where(den > 0, den, 1.0)[..., None]
                   if normalise else num).reshape(nh, d_v)
            s = lax.dynamic_update_slice(
                s, jnp.where(live, s1, s_r)[None, None].astype(s.dtype), where)
            z = lax.dynamic_update_slice(
                z, jnp.where(live, z1, z_r)[None, None].astype(z.dtype), where)
            return s, z, o.at[i].set(jnp.where(live, o_i, 0.0))

        s, z, o = lax.fori_loop(
            0, t, token, (s, z, jnp.zeros((t, nh, d_v), f32)))
        return o, s, z

    return packed


@contextlib.contextmanager
def broken(control: str | None):
    """The program with ONE thing changed for the length of the block."""
    import jax.numpy as jnp

    import llm_np_cp_tpu.serve.engine as engine_mod
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.ops import retention as real_ops

    real_packed = real_ops.retention_packed
    real_shapes = ModelConfig.state_shapes
    saved = engine_mod.retention_ops
    try:
        if control == "bf16_state":
            def shapes(self, slots, dtype):
                return {name: (shape, "bfloat16" if name.startswith("retention")
                               else dt)
                        for name, (shape, dt) in real_shapes(self, slots, dtype).items()}
            ModelConfig.state_shapes = shapes
        elif control is not None:
            import types

            def packed(s, z, layer, *a, fresh, **kw):
                if control == "no_normaliser":
                    return slow_packed(False)(s, z, layer, *a, fresh=fresh, **kw)
                if control == "state_zeroed":
                    fresh = jnp.ones_like(fresh)
                if control == "fresh_ignored":
                    fresh = jnp.zeros_like(fresh)
                return real_packed(s, z, layer, *a, fresh=fresh, **kw)

            engine_mod.retention_ops = types.SimpleNamespace(
                retention_packed=packed, CHUNK=real_ops.CHUNK,
                state_update_impl=real_ops.state_update_impl)
        yield
    finally:
        ModelConfig.state_shapes = real_shapes
        engine_mod.retention_ops = saved


def compare(ref_logits, got, tokens) -> dict:
    """``off`` and ``gap`` of ``got [n, V]`` (the logits ``tokens [n]`` were
    drawn from) against the reference's at the same positions."""
    import numpy as np

    ref_logits, got = np.asarray(ref_logits), np.asarray(got)
    spread = ref_logits.max(-1) - ref_logits.mean(-1)
    off = np.abs(got - ref_logits).max(-1) / spread
    gap = (ref_logits.max(-1) - ref_logits[np.arange(len(tokens)), tokens]) / spread
    return dict(off=off, gap=gap)


def serve(engine_cls, params, config, *, prompts, new, slots, block, chunk,
          dtype, keep):
    """Serve ``prompts`` (``new[i]`` tokens each); the logits of the kept
    requests' served tokens."""
    import jax
    import numpy as np

    import llm_np_cp_tpu.serve.engine as engine_mod
    from llm_np_cp_tpu.ops.sampling import Sampler

    ticks: list = []
    real = engine_mod.final_logits

    def probed(p, x, cfg, **kw):
        logits = real(p, x, cfg, **kw)
        jax.debug.callback(lambda a: ticks.append(np.asarray(a)), logits)
        return logits

    engine_mod.final_logits = probed
    try:
        engine = engine_cls(
            params, config, sampler=Sampler(kind="greedy"), max_slots=slots,
            num_blocks=0, block_size=block, max_seq_len=1024,
            prefill_chunk=chunk, cache_dtype=dtype, sample_epilogue="off")
        reqs = [engine.submit(p, max_new_tokens=n, seed=i)
                for i, (p, n) in enumerate(zip(prompts, new))]
        got = {i: [] for i in keep}
        slot_of: dict = {}
        while True:
            before = [len(r.generated) for r in reqs]
            more = engine.step()
            jax.effects_barrier()
            for i, r in enumerate(reqs):
                if i in got and len(r.generated) > before[i]:
                    slot = r.slot if r.slot is not None and r.slot >= 0 else slot_of[i]
                    got[i].append(ticks[-1][slot, 0])
                if r.slot is not None and r.slot >= 0:
                    slot_of[i] = r.slot
            del ticks[:-1]
            if not more:
                break
        impl = engine.retention_state_impl
        return reqs, {i: np.stack(v) for i, v in got.items()}, impl
    finally:
        engine_mod.final_logits = real


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--new", type=int, default=48)
    ap.add_argument("--samples", type=int, default=6)
    ap.add_argument("--forward-len", type=int, default=384)
    ap.add_argument("--long", type=int, default=8448,
                    help="0 leaves the long-context part out")
    ap.add_argument("--control", action="append", default=[], choices=CONTROLS)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--data-root", default=None,
                    help="directory that holds BENCHMARK.json and its data "
                    "files (a rehearsal points this at a tiny copy)")
    args = ap.parse_args()

    import run as harness
    import traffic as traffic_mod
    from llm_np_cp_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_brumby as ref
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.models.transformer import forward
    from llm_np_cp_tpu.serve import ServeEngine

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"parity_brumby.py: no TPU ({dev.platform}); no result", file=sys.stderr)
        return 2
    spec = harness.load_spec(Path(args.data_root or harness.ROOT), args.workload)
    hf = spec["config"]
    serve_cfg = hf.get("serve", {})
    config = ModelConfig.from_hf_dict(hf)
    dtype = jnp.bfloat16 if serve_cfg.get("dtype", "bf16") == "bf16" else jnp.float32
    block = int(serve_cfg.get("block_size", 64))
    chunk = min(block * 2, 256)
    params = harness.make_weights(config, args.seed, dtype, False)
    rng = np.random.default_rng(args.seed)
    p_rng = spec["traffic"]["prompt_tokens"]
    _, m_max = traffic_mod.limits(spec["traffic"])
    out: dict = dict(workload=args.workload, seed=args.seed, device=dev.device_kind,
                     limits=dict(off_mean=OFF_MEAN_LIMIT, off_p99=OFF_P99_LIMIT))
    failures: list[str] = []
    tail = jax.jit(lambda p, x: ref.logits_of(p, hf, x))
    body = jax.jit(lambda p, ids: ref.hidden_states(p, hf, ids))

    def reference(seq, lo, hi):
        """The reference's logits at positions ``lo .. hi - 1`` of ``seq``,
        computed on the sequence padded to a multiple of 256 tokens (causal:
        what follows a position cannot change it), so that a few compiled
        programs serve every length."""
        ids = np.zeros((-(-len(seq) // 256) * 256,), np.int32)
        ids[:len(seq)] = seq
        x = body(params, jnp.asarray(ids))
        # (the tail over a fixed number of rows: one program)
        rows = np.zeros((-(-(hi - lo) // 64) * 64, x.shape[-1]), np.float32)
        rows[:hi - lo] = np.asarray(x[lo:hi])
        return np.asarray(tail(params, jnp.asarray(rows)))[:hi - lo]

    def verdict(name, off) -> dict:
        got = summary(off)
        ok = got["mean"] <= OFF_MEAN_LIMIT and got["p99"] <= OFF_P99_LIMIT
        print(f"[parity] {name}: off mean {got['mean']:.4f} (limit "
              f"{OFF_MEAN_LIMIT}) p99 {got['p99']:.4f} (limit {OFF_P99_LIMIT}) "
              f"worst {got['worst']:.4f} -> {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(name)
        return dict(got, ok=ok)

    # (a) the plain forward
    t0 = time.time()
    ids = rng.integers(1, config.vocab_size, args.forward_len)
    got = np.asarray(jax.jit(lambda p, i: forward(p, i, config)[0])(
        params, jnp.asarray(ids[None], jnp.int32))[0])
    want = reference(ids, 0, len(ids))
    nxt = np.concatenate([ids[1:], ids[:1]])
    res = compare(want, got, nxt)
    out["forward"] = dict(verdict("forward", res["off"]), tokens=len(ids),
                          seconds=time.time() - t0)
    del got, want

    # (b) the served path, and each control of it
    n_req = args.slots + max(args.slots // 4, 1)
    prompts = [rng.integers(1, config.vocab_size, int(rng.integers(
        p_rng["min"], p_rng["max"] + 1))).tolist() for _ in range(n_req)]
    new = [args.new] * n_req
    new[0] = m_max  # the mix's longest answer, once
    keep = sorted({0, *range(1, n_req, max(n_req // args.samples, 1))} | {
        n_req - 1, n_req - 2})
    for control in [None, *args.control]:
        name = control or "served"
        t0 = time.time()
        with broken(control):
            reqs, logits, impl = serve(
                ServeEngine, params, config, prompts=prompts, new=new,
                slots=args.slots, block=block, chunk=chunk, dtype=dtype, keep=keep)
        offs, gaps = [], []
        for i in keep:
            r = reqs[i]
            seq = list(r.prompt) + list(r.generated)
            p = len(r.prompt)
            res = compare(reference(seq, p - 1, p - 1 + len(r.generated)),
                          logits[i], np.asarray(r.generated))
            offs.append(res["off"]), gaps.append(res["gap"])
        off, gap = np.concatenate(offs), np.concatenate(gaps)
        out[name] = dict(
            verdict(name, off), gap=summary(gap), requests=n_req, compared=len(keep),
            tokens=int(off.size), state_update=impl, slots=args.slots,
            prompts=[min(map(len, prompts)), max(map(len, prompts))],
            seconds=time.time() - t0)
        if control is not None:
            # a control that fails is the limits at work, not the program's
            # fault; one of ``MUST_FAIL`` that passes is the limits' fault
            if name in failures:
                failures.remove(name)
            else:
                print(f"[parity] control {control} was NOT seen by the limits")
                if control in MUST_FAIL:
                    failures.append(f"{name} (a control the limits must see)")

    # (c) a context past the state's own size in tokens of K/V
    if args.long:
        t0 = time.time()
        prompt = rng.integers(1, config.vocab_size, args.long).tolist()
        reqs, logits, impl = serve(
            ServeEngine, params, config, prompts=[prompt], new=[args.new],
            slots=args.slots, block=block, chunk=chunk, dtype=dtype, keep=[0])
        r = reqs[0]
        seq = list(r.prompt) + list(r.generated)
        res = compare(reference(seq, args.long - 1, len(seq) - 1), logits[0],
                      np.asarray(r.generated))
        out["long"] = dict(verdict("long", res["off"]), gap=summary(res["gap"]),
                           context=len(seq), state_update=impl,
                           seconds=time.time() - t0)

    path = spec["dir"] / "out" / f"{args.workload}-{args.seed}.parity.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps({k: ({kk: vv for kk, vv in v.items() if kk != "gap"}
                          if isinstance(v, dict) else v) for k, v in out.items()}))
    print(f"[parity] {'FAILED: ' + ', '.join(failures) if failures else 'passed'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
