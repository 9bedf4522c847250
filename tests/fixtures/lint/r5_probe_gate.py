"""R5 bite fixture: a Pallas kernel reached without its probe gate, and
a gated selection with no fallback sibling.  Parsed only."""

from llm_np_cp_tpu.ops.pallas import flash_attention as fa_mod
from llm_np_cp_tpu.ops.pallas.decode_attention import (
    ragged_paged_attention,
)
from llm_np_cp_tpu.ops.pallas.latent_attention import (
    ragged_latent_attention,
)
from llm_np_cp_tpu.ops.pallas.sample_epilogue import sample_epilogue
from llm_np_cp_tpu.ops.pallas.support import kernel_available


class BadEngine:
    def decode(self, q, pages, tables, lengths, pads):
        # unconditional kernel call — no probe, no fallback
        return ragged_latent_attention(q, pages, tables, lengths, pads)  # BITE

    def mixed(self, q, pages, meta):
        if kernel_available("ragged_paged_attention"):
            # probe-gated but the conditional dead-ends — no XLA sibling
            # branch to degrade to
            return ragged_paged_attention(q, pages, pages, *meta)  # BITE

    def prefill(self, q, k, v):
        # module-attribute access must not bypass the rule
        return fa_mod.flash_attention(q, k, v, scale=0.1)  # BITE

    def sample(self, x, gamma, w):
        # the fused sampling epilogue is probe-gated like every kernel:
        # an unconditional call must bite (R5 parses the gated-kernel
        # set out of _probe, so the new probes cover it automatically)
        return sample_epilogue(x, gamma, w, tied=True, eps=1e-6)  # BITE

    def build(self):
        use_kernel = kernel_available("ragged_paged_attention")

        def step(q, pages, meta):
            # a nested function that REBINDS the builder's gated name
            # shadows it: the test below reads no gate
            use_kernel = bool(meta)
            if use_kernel:
                return ragged_paged_attention(q, pages, pages, *meta)  # BITE
            return q

        return step if use_kernel else None
