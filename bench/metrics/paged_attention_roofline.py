"""Paged attention kernels: least time for each step's useful attention
work (QK and PV operations at the bf16 peak, or each slot's cached KV
read once at HBM bandwidth, whichever is larger) over the device time of
every kernel named ``paged_*attention``, %."""
import harness as H


def read(run):
    if not run.trace or not run.steps or run.peaks is None:
        return None
    busy = H.kernel_seconds(run, ["paged_attention", "paged_packed_attention"])
    if busy <= 0:
        return None
    p = run.peaks
    least = sum(max(o / p["bf16_flops"], b / p["hbm_bytes_per_s"])
                for o, b in H.step_work(run, "paged_attention"))
    return 100.0 * least / busy
