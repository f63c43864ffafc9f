"""How tight the expert layers' grouped products walk around the rows that
are used: the rows the first product of each layer walked
(`moe_rows_walked`: the grouped-matmul kernel's row tiles that hold rows
times the tile's rows, or the whole sorted buffer where `ragged_dot` runs,
summed over layers and steps) over the assignments held by a valid lane
(`moe_assignments_held`), both from the program's own counters over the
window.  1 is a walk with no junk row; a decode pass of 16 rows an expert
against tiles of 128 reads about 8.  None from a program without the
counter."""


def read(trace, counters, cell):
    held = counters.get("moe_assignments_held", 0)
    if "moe_rows_walked" not in counters or not held:
        return None
    return counters["moe_rows_walked"] / held
