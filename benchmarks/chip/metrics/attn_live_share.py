"""Share of the paged pool's block slots that decode attention reads: the
KV pages the running rows' attention read over the window
(``serve.attn_live_pages``, counted by the engine from each row's length)
over the window's decode steps (``serve.decode_steps``) times the rows
(``num_slots``) times the block slots a row has (``max_len`` over
``block_size``).  The rest of the slots are work a kernel that walks every
slot would do for nothing.  Layer: kernels.  Moves ``tokens_per_s``."""


def read(ctx):
    pages, steps = ctx.obs.get("serve.attn_live_pages"), ctx.obs.get("serve.decode_steps")
    if pages is None or not steps:
        return None
    e = ctx.cfg["engine"]
    slots = e["num_slots"] * -(-e["max_len"] // e["block_size"])
    return 100.0 * pages / (steps * slots)
