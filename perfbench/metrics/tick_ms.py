"""engine: wall time of one ``ServingEngine.tick()`` with the harness's own
work between ticks, on the harness clock: the window over its ticks."""


def read(run):
    if not run.window_ticks:
        return None
    return 1e3 * run.window_s / run.window_ticks
