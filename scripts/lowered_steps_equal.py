"""Is a change's train step the parent's program? Lower both for a described
v5e and compare: the text outside the Mosaic kernels' serialized bodies word
for word, and each body parsed and printed WITHOUT source locations (a body
holds the Python call stack of every operation, so any line that moved in
``models/mpt.py`` or an ``ops/`` file changes every body's bytes and nothing
of the program).

    JAX_PLATFORMS=cpu python scripts/lowered_steps_equal.py --lower ROOT PRESET OUT.txt
    JAX_PLATFORMS=cpu python scripts/lowered_steps_equal.py --compare A.txt B.txt

``--lower`` writes the step of ``PRESET`` as the checkout at ``ROOT`` lowers
it (``tests/test_tpu_compile._lower_train_step`` of that checkout: run it once
in a copy of the parent commit and once here); ``--compare`` exits 0 where the
two are one program. No chip is needed.
"""

from __future__ import annotations

import base64
import os
import re
import sys

BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def lower(root: str, preset: str, out: str) -> int:
    os.chdir(root)
    sys.path.insert(0, root)
    from photon_tpu.config import load_preset
    from photon_tpu.parallel.topo import abstract_tpu_devices
    from tests import test_tpu_compile

    class Patch:  # what the test's ``monkeypatch`` does, for good
        def setattr(self, obj, name, value):
            setattr(obj, name, value)

    lowered, _ = test_tpu_compile._lower_train_step(
        load_preset(preset), abstract_tpu_devices("v5e:2x2x1")[:1], Patch())
    with open(out, "w") as f:
        f.write(lowered.as_text())
    return 0


def without_locations(body: str) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # the serialized, versioned dialect
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)


def compare(a_path: str, b_path: str) -> int:
    a, b = (open(p).read() for p in (a_path, b_path))
    bodies_a, bodies_b = BODY.findall(a), BODY.findall(b)
    outside = BODY.sub("BODY", a) == BODY.sub("BODY", b)
    same = sum(without_locations(x) == without_locations(y) for x, y in zip(bodies_a, bodies_b))
    print(f"outside the kernels' bodies equal: {outside}; kernels {len(bodies_a)} / "
          f"{len(bodies_b)}, bodies equal without locations: {same}")
    return 0 if outside and len(bodies_a) == len(bodies_b) == same else 1


if __name__ == "__main__":
    mode, *rest = sys.argv[1:]
    sys.exit({"--lower": lower, "--compare": compare}[mode](*rest))
