"""Share of the decode step's device time spent moving the KV cache for
attention, in percent: the self time of the ops under the
`kv_cache_write` scope (the new token's K/V written into the cache) or
the `kv_relayout` scope (the cache relaid into the paged kernel's
`[B, T, Hkv·D]` layout) over the `serve_step` program's device time.
Read only where at least 99% of that time joined its HLO instruction and
the program names its layers."""
from benchmarks.chip import scopes as S


def read(ctx):
    t = S.program_s(ctx, "kv_cache.decode")
    if t is None:
        return None
    return 100.0 * ctx.trace.scope_s(S.KV_CACHE, S.DECODE) / t
