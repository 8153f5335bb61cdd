"""Share of the window's prompt tokens restored from the prefix cache
(``EngineReport.prefill_tokens_saved / prompt_tokens``)."""


def read(run):
    rep = run.window.report
    if rep.prompt_tokens <= 0:
        return None
    return 100.0 * rep.prefill_tokens_saved / rep.prompt_tokens
