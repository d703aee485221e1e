"""study_load_s: seconds per study in the window that the gang call
spends in the backend compile, or loading the program from the
persistent compilation cache: the program's span ``papas.gang.compile``
(``train/ensemble.py``). Moves ``study_makespan_s``."""
from benchmarks.chip import scopes


def read(ctx):
    found = scopes.program_study_spans(ctx)
    return None if found is None else found["load_s"]
