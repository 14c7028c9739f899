"""Share of the traced window in which the GRPO update program ran on the
device (by program name in the trace)."""

NEEDLES = ("grpo.update", "_update_impl")


def read(run):
    sec, n = run["trace"].module_seconds(*NEEDLES)
    if n == 0:
        return None
    return 100.0 * sec / run["trace"].window_s
