"""Model step: the model operations of the prompts committed and the
tokens decoded in the traced window's steps (`flops.prefill_flops`,
`flops.decode_flops`), over the window's seconds times chips times the
chip's bf16 peak."""

import flops


def read(run):
    if run.trace is None or not run.peaks:
        return None
    lo, hi = run.trace_window()
    work = 0
    for s in run.traced_steps():
        work += sum(flops.prefill_flops(run.spec, p, img)
                    for p, img in s.prefills)
        work += sum(flops.decode_flops(run.spec, p) for p in s.decode_pos)
    if not work:
        return None
    secs = (hi - lo) * 1e-9
    return 100.0 * work / (secs * run.chips
                           * run.peaks["bf16_flops_per_s"])
