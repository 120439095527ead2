"""Runtime: what a thread pays to get the interpreter back. The program's
heartbeat sleeps 20 ms at a time and takes, as it wakes, how late it is —
a worker coming out of a device fetch needs the GIL back the same way; each
``runtime.beat`` span carries a second's sum (``late_ms``) and count
(``ticks``). Sum of the sums over sum of the counts: the mean wake-up. None
where the window holds no beat."""


def read(ctx):
    beats = [s["tags"] for s in ctx["spans"]
             if s["name"] == "runtime.beat" and "ticks" in s["tags"]]
    ticks = sum(float(t["ticks"]) for t in beats)
    if ticks <= 0:
        return None
    return sum(float(t.get("late_ms", 0.0)) for t in beats) / ticks
