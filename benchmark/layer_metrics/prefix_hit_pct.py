"""Share of the prompt tokens admitted in the window that the prefix
index served from resident blocks (``prefix_hit_rate()``'s counters,
differenced over the window)."""


def read(run):
    c = run["counters"]
    if not c.get("prompt_tokens_admitted"):
        return None
    return 100.0 * c["prefix_matched_tokens"] / c["prompt_tokens_admitted"]
