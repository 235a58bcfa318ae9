"""Share of prompt tokens whose prefill the prefix cache saved in the
window: the engine's ``prefill_tokens_saved / prompt_tokens_total`` (%)."""


def read(run):
    c = run.counters
    if not c.get("prompt_tokens_total"):
        return None
    return 100.0 * c["prefill_tokens_saved"] / c["prompt_tokens_total"]
