"""The program's own spans (``deepspeed_tpu.utils.tracing``), as the readers
of the program-span metrics see them. A program without the recorder (the
parent of the PR that added it) has no spans: every reader then returns None
and its metric is left out of the line."""


def spans(name=None):
    """The recorder's spans, oldest first; with ``name`` only those. None
    where the program has no recorder or it recorded nothing."""
    try:
        from deepspeed_tpu.utils import tracing
    except ImportError:
        return None
    found = tracing.snapshot()
    if not found:
        return None
    return [s for s in found if name is None or s.name == name]


def fetch_ns(root, all_spans, name):
    """Summed duration of the spans called ``name`` below span ``root``."""
    from deepspeed_tpu.utils import tracing

    return sum(s.end - s.start for s in tracing.descendants(all_spans, root.id)
               if s.name == name)
