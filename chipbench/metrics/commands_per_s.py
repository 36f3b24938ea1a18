"""Real (unpadded) commands of every trace whose answer the client
received in the window, over the window's seconds (host clock)."""


def read(run):
    c = run.outcome.counters
    if "real_commands" not in c or run.outcome.window_s <= 0:
        return None
    return c["real_commands"] / run.outcome.window_s
