"""A reader added by files alone (tests load it through the manifest)."""


def read(ctx):
    late = ctx.get("late_ms")
    return max(late) if late else None
