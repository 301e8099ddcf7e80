"""``paged_fused_sparse_attention_hm``'s share of its roofline: the least
time the select-and-attend work the traced steps needed could take
(bench/work.py), over the kernel's summed device time, in %."""

from bench import work

# the kernel's op as the TPU trace names it (`%paged_fused_sparse_attention_hm.<n>`)
KERNEL = "paged_fused_sparse_attention_hm"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if spent <= 0:
        return None
    need = sum(work.roofline_s(w["attend_flops"], w["attend_bytes"], run.peak)
               for w in run.step_work)
    return 100.0 * need / spent
