"""study_lower_s: seconds per study in the window that the gang call
spends building its program and tracing and lowering it: the program's
spans ``papas.gang.build`` and ``papas.gang.lower``
(``train/ensemble.py``). Moves ``study_makespan_s``."""
from benchmarks.chip import scopes


def read(ctx):
    found = scopes.program_study_spans(ctx)
    return None if found is None else found["lower_s"]
