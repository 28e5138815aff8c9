"""Record the small TPU trace of the trainer's stages that
``test_host_spans.py`` reads:

    python3 tests/bench_chip/record_trainer_trace.py <out.xplane.pb>

It runs ``train_dml_distributed`` at a tiny size (one worker, d_in 512,
d_out 128, 128 pairs per step gathered from a host store, 4 steps, each
one logged) and, as the benchmark's training cells do, starts the JAX
profiler (its Python tracer off) and opens the window annotation from the
step hook at step 1, then closes both from the hook at step 3. So the
window holds the batch and the step of steps 2 and 3, and step 2 whole
with its ``train.log``, in which the hook compiles and runs a small
program of its own: the window holds one compile.
The store on the host (no gather programs), the host tracer at level 1
and leaving out the ``/host:metadata`` plane (the HLO of every program the
process ran, which the reader never looks at) keep the file small. Run it
on a TPU; it writes the profiler's ``.xplane.pb``, less that plane, to the
path given.
"""

import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness.trace import WINDOW  # noqa: E402
from repro.core import dml  # noqa: E402
from repro.core.ps import sync  # noqa: E402
from repro.core.ps.trainer import (DMLTrainConfig,  # noqa: E402
                                   train_dml_distributed)
from repro.data.pairs import IndexPairSource, sample_pair_indices  # noqa: E402

START, COMPILE, STOP = 1, 2, 3
DROPPED = b"/host:metadata"


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def without_plane(space: bytes, name: bytes) -> bytes:
    """The serialized ``XSpace`` less its planes named ``name``. Its
    planes are field 1 (length-delimited ``XPlane`` messages), each
    plane's name is the plane's field 2; every other field is kept as
    it was."""
    out, i = bytearray(), 0
    while i < len(space):
        start = i
        tag, i = _varint(space, i)
        kind = tag & 7
        if kind == 0:
            _, i = _varint(space, i)
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        elif kind == 2:
            n, j = _varint(space, i)
            i = j + n
            if tag >> 3 == 1 and _plane_name(space[j:i]) == name:
                continue
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        out += space[start:i]
    return bytes(out)


def _plane_name(plane: bytes) -> bytes:
    i = 0
    while i < len(plane):
        tag, i = _varint(plane, i)
        kind = tag & 7
        if kind == 2:
            n, j = _varint(plane, i)
            if tag >> 3 == 2:
                return plane[j:j + n]
            i = j + n
        elif kind == 0:
            _, i = _varint(plane, i)
        else:
            i += 8 if kind == 1 else 4
    return b""


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trainer_trace.py: no TPU")
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 8, 1024)
    feats = rng.rand(1024, 512).astype(np.float32)
    pool = sample_pair_indices(labels, 2000, 2000, seed=0)
    cfg = DMLTrainConfig(dml=dml.DMLConfig(feat_dim=512, proj_dim=128),
                         ps=sync.PSConfig(n_workers=1, sync="bsp"),
                         batch_size=128, steps=STOP + 1, lr=1e-2,
                         log_every=1)
    out_dir = tempfile.mkdtemp()
    window = []

    def hook(t, L):
        if t == START:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(out_dir, profiler_options=opts)
            window.append(jax.profiler.TraceAnnotation(WINDOW))
            window[0].__enter__()
        elif t == COMPILE:
            jax.jit(lambda x: jnp.tanh(x) * 3.0)(L).block_until_ready()
        elif t == STOP:
            window[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    train_dml_distributed(cfg, IndexPairSource(feats, pool), step_hook=hook)
    path = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f:
        space = f.read()
    with open(sys.argv[1], "wb") as f:
        f.write(without_plane(space, DROPPED))
    shutil.rmtree(out_dir)
    print(f"{sys.argv[1]}: {os.path.getsize(sys.argv[1])} bytes")


if __name__ == "__main__":
    main()
