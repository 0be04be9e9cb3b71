"""A benchmark cell's K=1 round program, lowered for a described v5e on the CPU,
as a hash: equal hashes on two trees say that a change left the cell's program
alone (`PERF.md` section 6 shows them for every PR that touches shared code).
A Mosaic kernel's serialized body carries the Python stack of its
`pallas_call` -- paths and line numbers --, so every `tpu_custom_call`'s
`backend_config` body is parsed and printed without debug info before the
hash; everything else of the lowered text is hashed as it stands.  Nothing
runs on a device and no weight is built: the experiment is the cell's own
(`benchmark/harness.py`), its data are written from the seed for their shapes,
and the program is traced with `jax.default_backend` reporting a TPU, so the
layers pick the kernels they pick there.

    JAX_PLATFORMS=cpu python scripts/lower_cell.py <cell> [<cell> ...]          # from the root of a tree
    (cd _parent && JAX_PLATFORMS=cpu python ../scripts/lower_cell.py <cell>)    # another tree, same script
    COMPILE=1 ... also compiles the program for the described chip and prints its memory and its custom calls

With another such process running, set ALLOW_MULTIPLE_LIBTPU_LOAD=1 in the shell.
4-10 s a cell; a compile 50-70 s.
"""
import base64
import hashlib
import json
import os
import re
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import harness
from heterofl_tpu import config as C
from heterofl_tpu.entry.common import FedExperiment, build_cli, cfg_from_args
from heterofl_tpu.parallel import RoundEngine, make_mesh

def strip_kernels(text):
    """The module's text with every tpu_custom_call's serialized Mosaic body replaced by the
    hash of the body printed without locations."""
    from jax._src.lib.mlir import ir
    from jax._src.interpreters import mlir as jmlir

    bodies = []

    def body(m):
        raw = m.group(1)
        try:
            cfg = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})", lambda h: chr(int(h.group(1), 16)), raw))
            data = base64.b64decode(cfg["custom_call_config"]["body"])
            ctx = jmlir.make_ir_context()
            ctx.allow_unregistered_dialects = True
            with ctx:
                mod = ir.Module.parse(data)
                printed = mod.operation.get_asm(enable_debug_info=False)
            bodies.append(printed)
            return 'backend_config = "MOSAIC:%s"' % hashlib.sha256(printed.encode()).hexdigest()[:16]
        except Exception as e:  # say so: an unparsed body would hash its call stack
            raise RuntimeError(f"cannot read a Mosaic body: {e}")

    out = re.sub(r'backend_config = "(\{[^"]*\})"', body, text)
    return out, len(bodies)


def lowered(cell_name, mesh, seed=1):
    """The text of the cell's K=1 round program lowered for ``mesh``'s one described chip."""
    cell, config = harness.load_cell(cell_name)
    with tempfile.TemporaryDirectory() as work:
        data_dir, out_dir = os.path.join(work, "data"), os.path.join(work, "out")
        harness.load_module("data", config["data"]["writer"]).write(
            data_dir, config["data_name"], seed, config["data"]["sizes"])
        argv = harness.experiment_argv(cell, config, seed, data_dir, out_dir)
        cfg = C.process_control(cfg_from_args(build_cli("benchmark").parse_args(argv)))
        exp = FedExperiment(cfg, cfg["init_seed"])
        data_split, label_split = exp.make_splits()
        exp.stage(data_split, label_split)
        data = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in exp.train_data]
        real = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            eng = RoundEngine(exp.model, exp.cfg, mesh)
            rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))

            def aval(t, sharding=rep):
                return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding)

            params = {k: aval(v) for k, v in jax.eval_shape(exp.model.init, jax.random.key(0)).items()}
            key = aval(jax.eval_shape(lambda: jax.random.key(0)))
            users = jax.ShapeDtypeStruct((exp.num_active,), jnp.int32, sharding=split)
            fix = (aval(jnp.asarray(eng.fix_rates)),) if eng.fix_rates is not None else ()
            args = (params, key, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep), users, users,
                    *(aval(t) for t in data), *fix)
            low = eng._build_train().lower(*args)
            text = low.as_text()
            if os.environ.get("COMPILE"):
                from heterofl_tpu.utils.compile_cache import no_persistent_cache
                t = time.time()
                with no_persistent_cache():
                    comp = low.compile()
                m = comp.memory_analysis()
                print(f"  compiled in {time.time() - t:.0f} s: temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, "
                      f"arguments {m.argument_size_in_bytes / 1e9:.2f}, output {m.output_size_in_bytes / 1e9:.2f}, "
                      f"code {m.generated_code_size_in_bytes / 1e6:.1f} MB", flush=True)
                hlo = comp.as_text()
                print("  custom calls:", sorted(set(re.findall(r"(\w+_(?:fwd|bwd))/pallas_call", hlo))), flush=True)
        finally:
            jax.default_backend = real
    return text


def main(cells):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(1, 1, devices=topo.devices[:1])
    for name in cells:
        t = time.time()
        text = lowered(name, mesh)
        stripped, kernels = strip_kernels(text)
        names = sorted(set(re.findall(r"(\w+_(?:fwd|bwd))/pallas_call", text)))
        print(f"{name}: {hashlib.sha256(stripped.encode()).hexdigest()[:8]} ({kernels} kernels"
              f"{' ' + ','.join(names) if names else ''}; {len(text) / 1e6:.1f} MB of text; "
              f"{time.time() - t:.0f} s)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
