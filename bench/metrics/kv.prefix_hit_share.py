"""Share of the prompt tokens admitted in the window, on both tiers, that
the KV pools served from their prefix index (the pools' ``tokens_saved``
counters over the prompt tokens of the window's ``admitted`` spans)."""


def read(run):
    w0, w1 = run.window
    ticks = {tk.t0 for tk in run.window_ticks}
    if not ticks:
        return None
    prompt = 0
    for tr in run.traces.values():
        adm = run.by_id.get(tr.rid)
        for sp in tr.spans:
            if sp.kind == "admitted" and sp.t0 in ticks and adm is not None:
                prompt += adm.bucket
    snaps = [(t, c) for t, c in run.saved]
    before = [c for t, c in snaps if t < w0]
    inside = [c for t, c in snaps if w0 <= t < w1]
    if not prompt or not inside:
        return None
    start = before[-1] if before else {k: (0, 0) for k in inside[-1]}
    saved = sum(inside[-1][k][0] - start[k][0] for k in inside[-1])
    return saved / prompt
