"""Kernels: the fused_select Pallas kernel's share of its roofline. The
least time per call is the larger of its FLOPs over the bf16 peak and
its bytes (harness/counts.py: logits read, masked logits written, one
mask row and the residue words per slot) over HBM bandwidth; the
kernel's time is its device time in the trace per call. Bandwidth bounds
it at these shapes."""
import sys


def read(run):
    if run.trace is None or run.peak is None:
        return None
    hits = [v for k, v in run.trace["ops"].items() if "fused_select" in k]
    secs, calls = sum(v[0] for v in hits), sum(v[1] for v in hits)
    if not calls or secs <= 0:
        return None
    slots = -(-run.mix["slots"] // 8) * 8       # the kernel's 8-row blocks
    nbytes = run.counts.fused_select_bytes(slots, run.model["vocab_size"],
                                           run.logit_bytes)
    least, bound = run.counts.roofline_s(0.0, nbytes, run.peak)
    print(f"fused_select: {calls} calls, {secs / calls * 1e6:.2f} us/call, "
          f"bound by {bound}, least {least * 1e6:.2f} us", file=sys.stderr)
    return 100.0 * least * calls / secs
