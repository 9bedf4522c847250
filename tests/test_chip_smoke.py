"""chip_smoke.py on the CPU: the rehearsal passes, and a run that should
not pass does not — no TPU, a probe forced to fail (the tick silently
resolves to the split path), a decode fault injected at dispatch (the
engine degrades to XLA and keeps serving).  None of these needs a chip;
the chip run itself is the builder's, through the chip tool.

The smoke runs in-process (``chip_smoke.main``): pytest's main thread is
where the server's SIGTERM drain handler must live anyway, and the
module import cost is paid once.
"""

import json
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import chip_smoke  # noqa: E402
from llm_np_cp_tpu.ops.pallas import support  # noqa: E402

pytestmark = pytest.mark.http  # binds 127.0.0.1:0 only


def run_smoke(tmp_path, capsys, *argv):
    report = tmp_path / "report.json"
    rc = chip_smoke.main([*argv, "--report", str(report)])
    out = capsys.readouterr().out
    facts = json.loads(report.read_text()) if report.exists() else None
    return rc, out, facts


def test_rehearsal_passes_and_says_so(tmp_path, capsys):
    rc, out, facts = run_smoke(tmp_path, capsys, "--rehearsal")
    assert rc == 0, out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    assert "platform: cpu" in out and "rehearsal: true" in out
    assert facts["resolution"] == {
        "tick": "unified", "ragged_attn_impl": "pallas",
        "epilogue_impl": "fused",
        "topology": "single chip",
    }
    assert facts["compiles_after_warmup"] == 0
    assert facts["decode_impl_degraded"] == 0 and facts["restarts_total"] == 0
    assert facts["shards_read_by"]["native C++"] > 0
    assert {"checkpoint write", "load+place", "warm-up (compile)",
            "requests", "drain"} <= set(facts["phases"])
    assert all(n["gap"] <= n["tolerance"] for n in facts["numerics"])


def test_without_rehearsal_a_cpu_run_fails_with_no_result(tmp_path, capsys):
    report = tmp_path / "report.json"
    rc = chip_smoke.main(["--report", str(report)])
    captured = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in captured.err and "'cpu'" in captured.err
    assert "{" not in captured.out  # no result line, no report
    assert not report.exists()


def test_forced_probe_failure_cannot_pass(tmp_path, capsys, monkeypatch):
    """With a refused ragged kernel the server serves from the XLA
    twins and exits 0; the smoke must not."""
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()
    try:
        rc, out, facts = run_smoke(tmp_path, capsys, "--rehearsal")
    finally:
        monkeypatch.undo()
        support._probe.cache_clear()
    assert rc == 1
    assert facts["resolution"]["tick"] == "unified"
    assert facts["resolution"]["ragged_attn_impl"] == "xla"
    assert any("want pallas" in f for f in facts["failures"])
    assert any("want fused" in f for f in facts["failures"])
    assert '"ok": true' not in out


def test_injected_decode_fault_cannot_pass(tmp_path, capsys, monkeypatch):
    """A dispatch-time fault degrades the tick to XLA and every request
    still finishes; decode_impl_degraded is what gives it away."""
    monkeypatch.setenv("LLMTPU_CHAOS_SPEC", "decode@2")
    try:
        rc, out, facts = run_smoke(tmp_path, capsys, "--rehearsal")
    finally:
        # the degradation ledger is process-wide by design
        support._RUNTIME_DISABLED.clear()
        from llm_np_cp_tpu.serve.faults import install

        install(None)
    assert rc == 1
    assert facts["decode_impl_degraded"] == 1
    assert any("decode_impl_degraded" in f for f in facts["failures"])
    assert any("fallback warnings" in f for f in facts["failures"])
    assert '"ok": true' not in out


def test_reference_forward_is_padding_invariant(tmp_path):
    """The smoke's reference pads every sequence to ONE length (one
    compile); the logits it reads must equal an unpadded forward's."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.models.transformer import forward
    from llm_np_cp_tpu.utils import synthetic

    p = chip_smoke.presets(rehearsal=True)
    synthetic.write_random_checkpoint(tmp_path, p["config"], seed=3,
                                      dtype=np.float32, workers=2)
    ref = chip_smoke.Reference(p, str(tmp_path))
    ids = [5, 9, 200, 17, 33, 4, 101]
    want, _ = forward(ref.params, jnp.asarray([ids], jnp.int32), p["config"],
                      logits_last_only=True)
    np.testing.assert_allclose(ref.next_logits(ids), np.asarray(want[0, -1]),
                               rtol=1e-5, atol=1e-5)
    g = ref.gaps(ids, [int(np.argmax(want[0, -1])), 0])
    assert g["gaps"][0] == 0 and g["gaps"][1] > 0 and g["finite"]


def test_divergence_passes_only_at_a_reference_near_tie():
    class Ref:
        def __init__(self, gaps):
            self._gaps = gaps

        def gaps(self, ids, tokens):
            self.asked = (ids, tokens)
            return dict(gaps=self._gaps, tolerance=0.1, finite=True)

    prompt, a = [1, 2], [7, 8, 9]
    assert chip_smoke.same_or_near_tie(Ref([0, 0]), prompt, a, a) == (
        True, "identical")
    tie = Ref([0.0, 0.04])
    ok, how = chip_smoke.same_or_near_tie(tie, prompt, a, [7, 8, 5])
    assert ok and "near-tie" in how and "token 2" in how
    # the reference is asked about the SHARED prefix and both candidates
    assert tie.asked == ([1, 2, 7, 8], [9, 5])
    ok, how = chip_smoke.same_or_near_tie(Ref([0.0, 2.5]), prompt, a, [7, 3, 9])
    assert not ok and "NOT a near-tie" in how
    ok, how = chip_smoke.same_or_near_tie(Ref([0, 0]), prompt, a, [7, 8])
    assert not ok and "lengths differ" in how
