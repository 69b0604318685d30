"""Block pool: prompt tokens served from shared blocks over prompt
tokens admitted in the window (engine counters), %."""


def read(run):
    adm = run.counter("admitted_prompt_tokens")
    return None if adm <= 0 else 100.0 * run.counter("prefix_hit_tokens") / adm
