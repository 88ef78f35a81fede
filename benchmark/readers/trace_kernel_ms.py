"""Device time of the Pallas kernels whose name starts with ``prefix``, in
milliseconds per ``per`` (a counter: dispatches). ``trace_op_ms`` sums every
kernel of the program; this one tells a family apart by the name the program
gives its ``pallas_call``."""

from benchmark.harness.trace import parse_op
from benchmark.readers import covered


def kernel_seconds(trace, prefix):
    """Summed device seconds of the kernels named ``prefix*``."""
    secs = 0.0
    for name, (s, _) in trace["ops"].items():
        instr, opcode = parse_op(name)
        if opcode == "kernel" and instr.startswith(prefix):
            secs += s
    return secs


def read(ctx, prefix, per):
    t, n = ctx["trace"], covered.per(ctx, per)
    if not t or not n:
        return None
    secs = kernel_seconds(t, prefix)
    return 1e3 * secs / n if secs else None
