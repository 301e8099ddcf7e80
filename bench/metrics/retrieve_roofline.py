"""``paged_fused_retrieve_hm``'s share of its roofline: the least time the
retrieval the traced steps needed could take (bench/work.py), over the
kernel's summed device time, in %."""

from bench import work

# the kernel's op as the TPU trace names it (`%paged_fused_retrieve_hm.<n>`)
KERNEL = "paged_fused_retrieve_hm"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if spent <= 0:
        return None
    need = sum(work.roofline_s(w["retrieve_flops"], w["retrieve_bytes"], run.peak)
               for w in run.step_work)
    return 100.0 * need / spent
