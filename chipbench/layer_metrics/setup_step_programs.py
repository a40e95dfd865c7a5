"""Programs the training step itself compiled (or loaded) before the traced
window: the program's `fused_step.launch` spans before it whose `compiled` is
true, i.e. in which the step's jit cache grew.  One is the least; each further
one is a whole-step compile that set-up pays (`chipbench/program_record.py`)."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.count_before("fused_step.launch", compiled=True)
