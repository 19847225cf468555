"""Gradient sync: the program's exact count of words each worker sends for
its row-sparse tensors per step (``sync/sparse_sent_words``), averaged
over the window's steps.  Nothing to read where no sparse tensor is
synced across workers."""


def reduce(run):
    vals = [c["sync/sparse_sent_words"] for c in run.counters
            if "sync/sparse_sent_words" in c]
    if not vals or max(vals) <= 0:
        return None
    return sum(vals) / len(vals)
