"""Share of admitted prompt tokens that the prefix cache served, from
``DecodeServer.stats``: 1 - prefill_tokens / prompt_tokens in the window."""


def read(observed):
    before, after = observed["stats_before"], observed["stats_after"]
    prompt = after["prompt_tokens"] - before["prompt_tokens"]
    if prompt <= 0:
        return None
    forwarded = after["prefill_tokens"] - before["prefill_tokens"]
    return 100.0 * (1.0 - forwarded / prompt)
