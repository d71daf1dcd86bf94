"""Kernels: share of its roofline that the fused tiered decode kernel
(``paged_decode_tiered``) reaches.

Each execution of the decode program (XLA module ``jit_step``) in the
traced span is paired, in order, with the decode step the host recorded.
The kernel's calls are the Mosaic custom calls inside it: the program
names them after the enclosing jitted call rather than the kernel, and
with fused decode on this kernel is the program's only Mosaic kernel.
For every decoded slot and layer, `flops.paged_decode_tiered_cost` at
the slot's position gives the operations and bytes its call needs; the
least time the chip's peaks allow, summed over those calls, is divided
by the device time of all the kernel's calls. Idle slots do no useful
work, so their calls only add time.
"""

import flops
import trace_reduce as TR

KERNEL = "paged_decode_tiered"


def is_kernel(name: str) -> bool:
    return name.startswith(KERNEL) or name.endswith(" mosaic")


def read(run):
    if run.trace is None or not run.peaks:
        return None
    lo, hi = run.trace_window()
    runs = TR.module_runs(run.trace, run.device, "jit_step", lo, hi)
    steps = [s for s in run.traced_steps() if s.decode_pos]
    if not runs or len(runs) != len(steps):
        return None
    calls = TR.ops_inside(run.trace, run.device, runs, is_kernel)
    layers = run.spec["num_hidden_layers"]
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    least = spent = 0.0
    for step, kernel_calls in zip(steps, calls):
        if len(kernel_calls) < layers * len(step.decode_pos):
            return None
        for p in step.decode_pos:
            ops, nbytes = flops.paged_decode_tiered_cost(
                run.spec, [p], run.hot_window)
            least += layers * max(ops / pf, nbytes / pb)
        spent += sum(c.dur for c in kernel_calls) * 1e-9
    return 100.0 * least / spent if spent else None
