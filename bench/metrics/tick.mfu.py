"""Model FLOP/s utilization of the whole serving step over the window (its
untraced part in a traced run): the useful FLOPs of every tick in it
(``bench/work.py``: live tokens and live context of both tiers, no padding)
over its seconds times the chip's peak bf16 FLOP/s, in percent."""


def read(run):
    if run.peak is None:
        return None
    w0, w1 = run.window
    flops = sum(f for tk, f, _ in run.work if w0 <= tk.t0 < w1)
    if flops <= 0:
        return None
    return 100.0 * flops / ((w1 - w0) * run.peak["bf16_flops_per_s"])
