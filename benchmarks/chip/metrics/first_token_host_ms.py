"""Host time the engine spends choosing an admission's first token, per
admission, over the whole window: ``serve.first_token_host_seconds`` over
``serve.first_tokens`` (the engine's counters; a greedy admission takes
an argmax, a sampled one top-p over the fetched logits, while the device
waits).  Layer: engine.  Moves ``ttft_p50_ms``."""


def read(ctx):
    seconds, admissions = (ctx.obs.get("serve.first_token_host_seconds"),
                           ctx.obs.get("serve.first_tokens"))
    if seconds is None or not admissions:
        return None
    return 1e3 * seconds / admissions
