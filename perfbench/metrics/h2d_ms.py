"""Data: host milliseconds from the start of a batch's placement until
every array of the placed batch is ready on its devices, the median over
the run's steps: the program's ``data.h2d`` span, closed by its observer
thread (the placement itself only queues the copies)."""
import program_spans


def read(ctx):
    return program_spans.median_ms("data.h2d")
