"""The whole decode step's share of the chip's roofline peak: the least
time the chip could take for the work every traced step needed (bench/
work.py; FLOPs over peak FLOP/s or bytes over peak bandwidth, whichever is
larger, and for decode that is bandwidth), summed over the steps, over the
traced window, in %."""

from bench import work


def read(run):
    t = run.trace
    if t is None or not run.step_work or t.window_s <= 0:
        return None
    need = sum(work.roofline_s(w["flops"], w["bytes"], run.peak)
               for w in run.step_work)
    return 100.0 * need / t.window_s
