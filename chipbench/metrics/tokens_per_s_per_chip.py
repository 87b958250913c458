"""Every token every node trained in the window, over the window's
seconds, over the chips (host clock, each step timed to its loss)."""


def read(rec):
    return rec.tokens_per_step * len(rec.step_s) / rec.window_s / rec.chips
