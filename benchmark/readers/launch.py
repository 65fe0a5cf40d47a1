def read(ctx):
    return ctx.get("launch_s")
