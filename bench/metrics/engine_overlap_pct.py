"""Share of the traced serving slice's device batches that the engine
dispatched while an earlier batch was still unread: 100 × Σ ``overlapped``
÷ the ``engine.dispatch`` spans that carry the attribute. None where no span
carries it (a program that never dispatches ahead)."""
import spans


def read(ctx):
    sp = spans.of(ctx)
    marks = [attrs["overlapped"] for _s, _e, attrs
             in (sp["events"]["engine.dispatch"] if sp else ())
             if "overlapped" in attrs]
    if not marks:
        return None
    return 100.0 * sum(marks) / len(marks)
