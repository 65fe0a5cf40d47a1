def read(ctx, field):
    d = ctx["drive"]
    xs = [s[field] for s in d["stats"] if field in s and d["t_open"] <= s["t"] <= d["t_close"]]
    return sum(xs) / len(xs) if xs else None
