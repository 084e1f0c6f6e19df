"""Model FLOP/s utilization of the window: the useful operations of the
prompts prefilled in it and of the tokens generated in it, at their real
lengths (the cell's architecture module's ``prefill_flops`` and
``decode_flops``: no padding), over the window and the chip's peak."""
from bench import flops
from bench.window import tokens_in_window


def read(ctx):
    c, arch = ctx.cell.config, ctx.cell.arch
    t0, t1 = ctx.window
    total = 0.0
    for r, _n, j0, j1 in tokens_in_window(ctx):
        p = len(r.prompt)
        if t0 <= r.first < t1:
            total += arch.prefill_flops(c, p)
        if j1 > j0:
            # Token j (1-based after the first) is made by a step whose
            # query attends p + j keys; the mean of a stretch is its middle.
            total += (j1 - j0) * arch.decode_flops(c, p + (j0 + j1) / 2)
    if total <= 0:
        return None
    return 100.0 * total / (t1 - t0) / flops.peaks(ctx.device_kind)["bf16_flops_per_s"]
