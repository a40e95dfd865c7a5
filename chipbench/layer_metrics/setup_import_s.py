"""Seconds of set-up that are the import: the union of the program's
`process.before_import` (process start to the package's first line: the
interpreter, `import jax`, and what the host script did first, here the
runner's `jax.devices()`, which starts the TPU runtime), `runtime.import` (the
package's import, first line to last) and `runtime.backend_start` (the
package's own first touch of the backend) spans (`chipbench/setup_record.py`)."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.covered_before_s(setup_record.IMPORT)
