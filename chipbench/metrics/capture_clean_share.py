"""Share of the session bytes a save fetches that were unchanged since the
previous save (KV rows and tokens not written since, in percent): the sum of
the ``capture_d2h`` span's ``clean_bytes`` label over the sum of its
``bytes`` label, over the traced window's spans that carry the label. What a
dirty-only capture could skip."""

import spans


def read(rec):
    d2h = [e["args"] for e in spans.named(spans.events(), "capture_d2h")
           if "clean_bytes" in e["args"]]
    total = sum(a["bytes"] for a in d2h)
    if not total:
        return None
    return 100.0 * sum(a["clean_bytes"] for a in d2h) / total
