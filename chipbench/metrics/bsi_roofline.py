"""BSI kernels' share (%) of their roofline: the least time the chip needs
for the BSI work those kernels did in a registration (``chipbench.work``,
bytes-bound) over their device time per pair in the traced window.  The
adjoint's work counts only where the adjoint ran as a kernel
(``bsi_adjoint*``): with an XLA adjoint its time is not in the kernels'."""

from chipbench import work


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    kernels = ctx.trace.kernel_s
    adjoint = sum(s for k, s in kernels.items() if k.startswith("bsi_adjoint"))
    forward = sum(kernels.values()) - adjoint
    cfg = ctx.cell.config
    fwd_work, adj_work = work.registration_bsi(
        cfg["volume"], cfg["tile"], cfg["levels"], cfg["iters"])
    need = ((work.roofline_seconds(fwd_work, ctx.peaks) if forward else 0.0)
            + (work.roofline_seconds(adj_work, ctx.peaks) if adjoint else 0.0))
    seconds = forward + adjoint
    return 100.0 * need / (seconds / ctx.pairs) if seconds > 0 else None
