"""Median `train.step`: TrainStep.__call__ from entry to the dispatch's return, the window's steps."""
from benchlib import program_spans as P

read = P.train_dispatch_ms_p50
