"""Device step: least time the chip needs for the window's model work,
over the traced window, %.  Linear layers are 2 ops per weight per
scheduled token at the int8 peak (counted once, not per phase or padded
row); attention (QK, PV over each token's context) and the output head
(2 * d_model * vocab per emitted token) at the bf16 peak."""
import harness as H


def read(run):
    if not run.trace or not run.steps or run.peaks is None:
        return None
    p = run.peaks
    lin = sum(o for o, _ in H.step_work(run, "tim_matmul"))
    att = sum(o for o, _ in H.step_work(run, "paged_attention"))
    head = 2.0 * run.dims.d * run.dims.vocab * len(run.window_tokens())
    least = lin / p["int8_ops"] + (att + head) / p["bf16_flops"]
    return 100.0 * least / run.trace["window_s"]
