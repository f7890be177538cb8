"""Filled share (%) of the batch slots dispatched in the window: requests
over the slots of their padded buckets."""


def read(ctx):
    b = ctx["served"].batches
    slots = sum(x["bucket"] for x in b)
    return 100.0 * sum(x["n_valid"] for x in b) / slots if slots else None
