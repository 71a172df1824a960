"""train loop: host-clock milliseconds per optimizer step over the window
(epochs end in one host fetch of the loss)."""


def read(name, ctx):
    return ctx.get("step_ms")
