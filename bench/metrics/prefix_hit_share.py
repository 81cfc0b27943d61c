"""prefix_hit_share: prompt tokens the KV cache served from reused prefix
blocks, as a share of all prompt tokens admitted in the window."""


def read(ctx):
    prompt = ctx.delta("prompt_tokens")
    return 100.0 * ctx.delta("prefix_hit_tokens") / prompt if prompt else None
