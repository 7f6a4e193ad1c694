"""Host time per decode token spent sampling the next token (the argmax
over the last logits), in us: the sum of ``Request.timing.sample_s`` over the
requests finished in the traced window, over the tokens they were served
after their first."""
from bench import timeline


def read(ctx, win, trace):
    return timeline.per_decode_token_us(win, "sample_s")
