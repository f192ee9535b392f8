"""Mean probes per micro-batch in the window (``MicroBatchStats.queries /
batches``).  At a fixed offered rate a batch fills with the probes that
arrive while the one before it is served, so slower service reads higher."""


def read(run):
    return run.queries / run.batches if run.batches else None
