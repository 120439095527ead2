"""Runtime: the longest time in the window for which the interpreter was
not to be had. A ``runtime.beat`` span's interval is its second's WORST
wake-up of the heartbeat (due -> woke): a full collection's length in a
sound window, seconds in one where the whole process stood still (the beat
then carries ``stall`` = 1 and what was held). None where the window holds
no beat."""


def read(ctx):
    beats = [s["dur_s"] for s in ctx["spans"] if s["name"] == "runtime.beat"]
    if not beats:
        return None
    return max(beats) * 1e3
