"""Programs this process had to compile because the persistent cache did
not hold them, counted by the program (``compile_cache.stats()``) at the
end of set-up.  0 on every run but a checkout's first."""


def read(ctx):
    return ctx.counters.get("compile.cache_misses")
