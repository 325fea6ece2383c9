"""`program_step_table`'s `mean` or `sum` of a field that a program's step
table may not have: a field newer than the program reads None (the metric
is left out), not a KeyError. The window's rows and the stall log are
`program_step_table`'s."""
from chipbench.readers import program_step_table


def read(result, stat, field, scale=1.0):
    rows = program_step_table.window_rows(result)
    if not rows or field not in rows[0]:
        return None
    return program_step_table.read(result, stat, field, scale)
