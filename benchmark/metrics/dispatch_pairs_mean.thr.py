"""Pairs answered per dispatch over the window: how far the batcher
fills the ladder (``/metrics`` completed requests over batches)."""
from benchmark.layer_metrics import dispatch_pairs_mean


def read(observed):
    return dispatch_pairs_mean(observed)
