"""Share of the decode lane's rows that held a request, over the window's
ticks and both tiers: the sum of the per-tick ``busy_slots@<tier>`` gauges
over the sum of ``decode_rows@<tier>``, the width the tick's decode lane
read K/V at.  A program without the ``decode_rows`` gauge reads every slot
of the tier, so its width is the tier's slot count."""
TIERS = (("S", "s_slots"), ("L", "l_slots"))


def read(run):
    ticks = run.window_ticks
    if not ticks:
        return None
    sv = run.config["serving"]
    busy = rows = 0
    for tk in ticks:
        for tier, slots in TIERS:
            busy += tk.gauges.get(f"busy_slots@{tier}", 0)
            rows += tk.gauges.get(f"decode_rows@{tier}", sv[slots])
    return busy / rows if rows else None
