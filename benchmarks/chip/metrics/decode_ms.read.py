"""Mean time of a serving-path decode, by the reader that leads it
(``repro.serve.decode``): plan, the serial gather and the S=1 launch."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.serve.decode")
    if "reads" not in run.parts or s is None or not s.count:
        return None
    return s.total_s / s.count * 1e3
