"""Passes a block committed in the window had cost, from the block schedule's
own counters: the committed blocks' own passes (their denoise passes and the
commit, counted at the commit) over the blocks committed.  S + 1 for a whole
block fixed a quota's lanes a pass, fewer where the confidence threshold
fixed more at once or the block held a prompt's tail; never more.  (The
window's slot-passes over its blocks would also count the passes of blocks
its edges cut.)  Nothing to read where the program runs no such schedule."""


def read(trace, counters, cell):
    blocks = counters.get("diffusion_blocks_committed", 0)
    if not blocks:
        return None
    return counters["diffusion_committed_block_passes"] / blocks
