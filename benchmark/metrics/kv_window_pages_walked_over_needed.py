"""Pages the sliding-window layers' attention WALKS over the pages that hold
the rows their valid lanes may attend, summed over the traced chunks: the
program's own pair on each chunk's `serve.dispatch` span
(`kv_pages_walked_window`: the batcher replays the kernel's bound on the
host; `kv_pages_window_needed`).  About 1 where the walk starts at the
window's first page (above it by the junk lanes' pages and the free slots'),
about 7 at this cell's depths where it starts at page 0."""
import program_spans


def read(trace, counters, cell):
    program = program_spans.for_cell(trace, cell)
    if program is None:
        return None
    pairs = [(s.ids["kv_pages_walked_window"], s.ids["kv_pages_window_needed"])
             for s in program.spans if s.name == "serve.dispatch"
             and "kv_pages_walked_window" in s.ids
             and "kv_pages_window_needed" in s.ids]
    needed = sum(n for _, n in pairs)
    return sum(walked for walked, _ in pairs) / needed if needed else None
