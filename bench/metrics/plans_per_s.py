"""Plans completed per second: every plan of the window over the window's
seconds (host clock)."""


def read(run):
    if run.cell.unit != "plans":
        return None
    return run.units / run.window_s
