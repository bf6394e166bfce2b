"""Share of the window's ``PipelinedLoader.__next__`` calls that found no
sampled batch waiting, in % (program span ``repro.loader.get``, attribute
``queue``)."""
from chipbench import program_trace as P


def read(run):
    gets = P.spans(run, __file__, "loader.get")
    if not gets:
        return None
    empty = sum(1 for s in gets if s.attrs.get("queue") == "empty")
    return 100.0 * empty / len(gets)
