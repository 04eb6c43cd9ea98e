"""Share of its memory roofline that the deleted-side bank pass reaches, in
%: the bytes the matching needs (each row matched read once, 12 B, and its
bank words written, 4 B a word) over the chip's HBM bandwidth, divided by
the summed device time of the bank-pass kernels' events. Rows are the
broker's own count of real rows matched (``BrokerStats.rows_matched``), not
padded tiles, so a change that cuts padding shows as a gain."""

from trace_reduce import roofline_share

# the deleted-side passes as the trace names them (the words pass, vmapped
# over frontiers, its segmented form over a frontier chain, and the
# refinement of virtual lanes); the cohort step's own lanes kernel is not one
KERNELS = ("triple_match_words_pallas", "triple_match_words_segmented_pallas",
           "lane_refine_pallas")


def bytes_needed(rows_matched: int, bank_words: int) -> int:
    return rows_matched * (12 + 4 * bank_words)


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    seconds = t.op_seconds(lambda e: any(k in e.short for k in KERNELS))
    rows = sum(s.rows_matched for s in run.stats)
    return roofline_share(bytes_needed(rows, run.bank_words), seconds,
                          run.peaks)
