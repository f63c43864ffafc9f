"""How tight the expert layers' sorted buffers sit around the rows that are
used: the rows of the buffers the layers took (`moe_rows_sorted`: the rung
of each layer's ladder, summed over layers and steps) over the assignments
held by a valid lane (`moe_assignments_held`), both from the program's own
counters over the window.  1 is a buffer with no junk row; a program that
sorts, gathers and combines all S*k assignments of an admission step reads
about 30 where 3 % of them are held.  None from a program without the
counter."""


def read(trace, counters, cell):
    held = counters.get("moe_assignments_held", 0)
    if "moe_rows_sorted" not in counters or not held:
        return None
    return counters["moe_rows_sorted"] / held
