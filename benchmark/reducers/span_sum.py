"""Sum of the benchmark's own set-up spans named in ``spans``."""


def read(run, params):
    have = [run["spans"][s] for s in params["spans"] if s in run["spans"]]
    return sum(have) if have else None
