"""Share of the prompt tokens admitted in the window that the prefix trie
served instead of prefill (the pool's hit-token counter over the prompt
tokens of the requests admitted).  Layer: KV cache.  Moves
``ttft_p50_ms``."""


def read(ctx):
    admitted = ctx.counts["admitted_tokens"]
    return 100.0 * ctx.counts["prefix_hit_tokens"] / admitted if admitted else None
