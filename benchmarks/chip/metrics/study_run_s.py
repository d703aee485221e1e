"""study_run_s: seconds per study in the window that the gang call
spends dispatching the members' program and fetching their losses: the
program's span ``papas.gang.run`` (``train/ensemble.py``).
Moves ``study_makespan_s``."""
from benchmarks.chip import scopes


def read(ctx):
    found = scopes.program_study_spans(ctx)
    return None if found is None else found["run_s"]
