"""What the roofline and whole-step readers under `layer_metrics/` share:
device seconds per step in picked operations, the cell's configuration with
the module that counts its work, the chip's peaks, and the program's
expert-load counters (empty where the program has none)."""
import os

from chipbench import run


def per_step_s(trace, keep):
    """Device 0's seconds per step in the operations `keep` picks; None where
    there are none."""
    steps = sum(name == "chipbench.dispatch" for name, _s, _e in trace.spans)
    busy = sum(e - s for name, s, e in trace.ops[0] if keep(name))
    return busy / steps if steps and busy else None


def config_of(cell):
    """(configuration, its module) of the cell file's `config`."""
    stem = os.path.join(run.HERE, "configs", cell["config"])
    return run.load_json(stem + ".json"), run.load_py(stem + ".py")


def peaks():
    import jax
    return run.peaks_of(jax.devices()[0].device_kind)


def expert_loads():
    """`mxnet_tpu.parallel.moe.expert_loads()`: rows per held expert of every
    routed layer on the last step; [] for a program from before the counter."""
    try:
        from mxnet_tpu.parallel.moe import expert_loads as read
    except ImportError:
        return []
    return read()


def routed_rows():
    return sum(sum(layer["rows"]) for layer in expert_loads())
