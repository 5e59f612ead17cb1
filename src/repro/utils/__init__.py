from repro.utils.timing import Timer, TimingStats, time_fn  # noqa: F401
