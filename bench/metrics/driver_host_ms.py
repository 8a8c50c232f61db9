"""Host time per round of the cohort driver's own work: the self time of
the program's ``staging`` and ``aggregation_fold`` spans (host numpy:
per-cohort mask staging and optimizer-bank gathers, and the scatter of
the cohort's optimizer states into the bank), in ms per round."""

SPANS = ("staging", "aggregation_fold")


def read(view):
    spans = sorted(view.spans, key=lambda r: r.start_ns)
    total, found = 0, False
    for i, r in enumerate(spans):
        if r.name not in SPANS:
            continue
        found = True
        # Self time: the span's duration less that of its direct children.
        end = r.start_ns + r.dur_ns
        inner = 0
        for c in spans[i + 1:]:
            if c.start_ns >= end:
                break
            if c.depth == r.depth + 1:
                inner += c.dur_ns
        total += r.dur_ns - inner
    return total / 1e6 / view.rounds if found else None
