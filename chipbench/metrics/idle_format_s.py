"""Device-idle seconds per query run under the trace whose cause is the
§3.2 format: the program's ``repro.format.decode`` and
``repro.format.encode`` spans (``spans.reduce``'s idle rule)."""


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not reduced["queries"] or not run.traced:
        return None
    return sum(s for cause, s in reduced["idle_causes"].items()
               if cause.startswith("repro.format.")) / len(run.traced)
