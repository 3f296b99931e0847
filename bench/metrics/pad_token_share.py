"""Share of the token lanes of real requests that are bucket padding, over
the window: ``serve_pad_tokens_total / (serve_tokens_total +
serve_pad_tokens_total)``, all op kinds.  Layer: scheduler
(``serve/scheduler.py``)."""


def read(ctx):
    real = sum(ctx.counters["serve_tokens_total"].values())
    pad = sum(ctx.counters["serve_pad_tokens_total"].values())
    if real + pad <= 0:
        return None
    return 100.0 * pad / (real + pad)
