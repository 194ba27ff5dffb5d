"""Block manager: prompt tokens found in the prefix cache during the window,
as a share of the prompt tokens submitted in it."""


def read(run):
    submitted = sum(r.prompt_len for r in run.requests
                    if run.in_window(r.sent))
    if not submitted or "prefix_tokens_saved" not in run.stats_after:
        return None
    saved = (run.stats_after["prefix_tokens_saved"]
             - run.stats_before["prefix_tokens_saved"])
    return 100.0 * saved / submitted
