"""Median host time of one engine scheduling round (``Engine.step``),
over the rounds of the window: admission, prompt chunks, one decode
burst and retirement, as the host clock sees them."""
import statistics


def read(name, r):
    rounds = r.get("rounds_s")
    return statistics.median(rounds) * 1e3 if rounds else None
