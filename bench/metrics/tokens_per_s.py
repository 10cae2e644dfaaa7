"""Output tokens stamped inside the window, over the window's length."""


def read(ctx):
    return ctx.nums["tokens"] / ctx.nums["seconds"]
