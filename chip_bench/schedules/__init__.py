"""Open-loop arrival schedules, one module per ``schedule`` that a traffic
mix names.  Each module has ``due_times(mix, seconds) -> np.ndarray``: the
send times in [0, seconds), from 0 and rising, fixed by the mix's own
parameters and never by the run's seed.
"""
