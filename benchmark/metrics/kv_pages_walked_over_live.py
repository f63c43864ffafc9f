"""Pages of the slots' tables the attention WALKS over the pages that are
LIVE (up to each occupied slot's frontier), summed over the traced chunks:
the program's own pair on each chunk's `serve.dispatch` span
(`kv_pages_walked`, `kv_pages_live`: the batcher replays the walk's bound
on the host).  1 where every slot stops at its own frontier, plus the free
slots' one page each; about 3 where every slot goes to the deepest one's."""
import program_spans


def read(trace, counters, cell):
    program = program_spans.for_cell(trace, cell)
    if program is None:
        return None
    pairs = [(s.ids["kv_pages_walked"], s.ids["kv_pages_live"])
             for s in program.spans if s.name == "serve.dispatch"
             and "kv_pages_walked" in s.ids and "kv_pages_live" in s.ids]
    live = sum(held for _, held in pairs)
    return sum(walked for walked, _ in pairs) / live if live else None
