"""The Mamba selective scan's share of the device's busy time in the
federated-LoRA cells.

The scan runs as two Pallas kernels (``kernels/selective_scan.py``), each a
custom call named after its jitted wrapper: ``mamba_scan_fwd`` (the
forward, the layer remat's recomputed forward and the evaluations) and
``mamba_scan_bwd`` (the adjoint).  Under the program's transformations the
instruction's name carries prefixes (``%jvp_vmap_jit_mamba_scan_fwd___.2``),
so an op counts when its own name, the text before `` = ``, holds either
wrapper's name and it is a TPU custom call.  Share = their summed device
time over the busy time of the traced window, in %.  A program without the
kernels (the associative scan) reads nothing.
"""
TPU_CALL = 'custom_call_target="tpu_custom_call"'
WRAPPERS = ("mamba_scan_fwd", "mamba_scan_bwd")


def scan_seconds(red) -> tuple[float, int]:
    """Summed device seconds and events of the scan kernels in ``red``."""
    secs, n = 0.0, 0
    for op, s in red.op_seconds.items():
        head = op.split(" = ", 1)[0]
        if TPU_CALL in op and any(w in head for w in WRAPPERS):
            secs += s
            n += red.op_counts[op]
    return secs, n


def read(ctx):
    red = ctx["trace"]
    if red is None or red.busy_s <= 0:
        return None
    secs, n = scan_seconds(red)
    if n == 0:
        return None
    return 100.0 * secs / red.busy_s
