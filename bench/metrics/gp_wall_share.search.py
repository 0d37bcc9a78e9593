"""Share of the window's wall time spent in the stacked GP's fits and
scorings (%): each `gp._fit_stack` and `gp._score_stack` call of the
window, waited for to its end (`run.Recorder`), over the whole window, by
the host clock.  It covers the whole window, where the profiler's trace
holds only its first second or so."""


def read(run):
    gp = run.wall_s.get("gp", 0.0)
    if gp <= 0 or run.window_wall_s <= 0:
        return None
    return 100.0 * gp / run.window_wall_s
