"""KV memory (serve/cache.py): prompt tokens reused from the prefix trie
over all prompt tokens admitted in the window, in %, from the engine's
n_prefill_tokens and n_prefill_tokens_skipped."""


def read(ctx):
    a, b = ctx.run.at_open, ctx.run.at_close
    reused = b["skipped"] - a["skipped"]
    computed = b["prefill"] - a["prefill"]
    total = reused + computed
    return 100.0 * reused / total if total else None
