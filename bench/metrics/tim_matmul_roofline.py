"""TiM matmul kernels: least time for the window's useful linear work
(per step the larger of its int8 operations at peak and its stored
weight bytes at HBM bandwidth) over the device time of every kernel
named ``tim_matmul*``, %."""
import harness as H


def read(run):
    if not run.trace or not run.steps or run.peaks is None:
        return None
    busy = H.kernel_seconds(run, ["tim_matmul"])
    if busy <= 0:
        return None
    p = run.peaks
    least = sum(max(o / p["int8_ops"], b / p["hbm_bytes_per_s"])
                for o, b in H.step_work(run, "tim_matmul"))
    return 100.0 * least / busy
