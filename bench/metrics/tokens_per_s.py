"""Tokens that traces emitted in the window, over the window's seconds
(host clock: the decode calls' sync times)."""


def read(ctx):
    return ctx.obs.window_tokens() / ctx.seconds
