"""Own device time of the operations named ``ragged_latent_attention`` (the
latent attention kernel of the ONE latent layer in each group of a
``ling_hybrid`` stack) as a share of device busy time in the profiler window,
in %: ``mla.attn_share``'s reading, in this stack's cell.  Another
architecture, or a program without the kernel, reads nothing."""
import devtrace

NEEDLES = ["ragged_latent_attention"]


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    v = devtrace.share_by_name(run.get("device_trace"), NEEDLES)
    return 100.0 * v if v else None
