"""study_gang_traces: gang programs traced and lowered per study in the
window: the number of the program's ``papas.gang.lower`` spans over the
number of studies. Moves ``study_makespan_s``."""
from benchmarks.chip import scopes


def read(ctx):
    found = scopes.program_study_spans(ctx)
    return None if found is None else found["gang_traces"]
