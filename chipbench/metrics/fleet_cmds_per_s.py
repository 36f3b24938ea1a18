"""Module x real-command charges completed in the window, over the
window's seconds (host clock)."""


def read(run):
    c = run.outcome.counters
    if "module_commands" not in c or run.outcome.window_s <= 0:
        return None
    return c["module_commands"] / run.outcome.window_s
