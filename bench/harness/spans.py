"""Summed durations of the window's ``repro.obs`` spans, by the level
the span's name starts with (``cascade:``, ``einsum:``, ``stage:``,
``seam:``), in seconds."""


def level_seconds(w):
    """{level: seconds}, or None on a run without spans."""
    if w.spans is None:
        return None
    out = {"cascade": 0.0, "einsum": 0.0, "stage": 0.0, "seam": 0.0}
    for ev in w.spans:
        if ev.get("ph") != "X":
            continue
        level = ev["name"].split(":", 1)[0]
        if level in out:
            out[level] += ev["dur"] / 1e6
    return out
