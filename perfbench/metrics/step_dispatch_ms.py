"""Step: host milliseconds the training thread spends enqueueing one step,
the median over the run's steps: the program's ``step.dispatch`` span
around the jitted step call."""
import program_spans


def read(ctx):
    return program_spans.median_ms("step.dispatch")
