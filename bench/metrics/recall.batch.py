"""recall.batch: recall@k of the sampled window outputs against the plain
reference (``bench/reference.py``), as ``checks.compare`` counts it."""


def read(ctx):
    return ctx.numbers.get("recall")
