"""Output tokens committed in the window over the window's length."""


def read(run):
    return sum(len(s.times) for s in run["served"]) / run["window_s"]
