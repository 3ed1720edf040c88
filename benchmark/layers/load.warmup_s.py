"""Warm-up: from the first warm-up request to a round that started no new
program (compiles on a cold cache, reads the cache on a warm one)."""


def read(ctx):
    return ctx["setup"]["warmup_s"]
