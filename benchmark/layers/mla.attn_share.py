"""Own device time of the operations named ``ragged_latent_attention`` (the
latent attention kernel of a ``deepseek_v3`` stack: one page of rows ``[c' |
k_pe]`` fetched once a step, read as K and as V by all heads) as a share of
device busy time in the profiler window, in %.  Its OWN name: the ragged
paged kernel and the experts' ``ragged-dot`` calls carry others.  A program
without the kernel reads nothing."""
import devtrace

NEEDLES = ["ragged_latent_attention"]


def read(run: dict) -> float | None:
    v = devtrace.share_by_name(run.get("device_trace"), NEEDLES)
    return 100.0 * v if v else None
