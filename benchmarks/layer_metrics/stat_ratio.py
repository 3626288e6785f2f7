"""One number of the run's stats over another (`_stats.lookup`'s dotted
paths): a level that the program totals over a window's steps, as a ratio
of two totals. Nothing where either is missing or the divisor is zero (a
program that counts neither)."""

from benchmarks.layer_metrics._stats import lookup


def read(ctx, of: str, per: str):
    count, over = lookup(ctx, of), lookup(ctx, per)
    if count is None or not over:
        return None
    return count / over
