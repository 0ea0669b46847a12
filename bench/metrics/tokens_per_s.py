"""Output tokens completed over the whole window, from its start to the
last request's completion: what a served token costs at this load."""


def read(run):
    return run.report.total_tokens / run.window_s
