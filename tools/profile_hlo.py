"""Dev tool: list the largest per-partition tensors in a dry-run cell's HLO.

    PYTHONPATH=src python tools/profile_hlo.py --arch jamba-1.5-large-398b \
        --shape train_4k --multipod --min-gb 0.3
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
import argparse
import re

import jax

from repro.configs import get_config
from repro.configs.shapes import SHAPES, input_specs
from repro.launch.mesh import make_dryrun_mesh
from repro.models import transformer as T
from repro.train import steps as ST

DT = {"f32": 4, "bf16": 2, "pred": 1, "s32": 4, "u32": 4, "s8": 1, "f16": 2}


def lower_cell(arch, shape_name, multipod, grad_accum=1):
    cfg = get_config(arch)
    mesh = make_dryrun_mesh(multi_pod=multipod)
    abstract = T.abstract_params(jax.random.PRNGKey(0), cfg)
    shape = SHAPES[shape_name]
    spec = input_specs(cfg, shape)
    with mesh:
        if shape.kind == "train":
            tc = ST.TrainConfig(grad_accum=grad_accum)
            jitted, _ = ST.build_sharded_train_step(
                cfg, tc, mesh, abstract_params=abstract)
            opt = ST.make_optimizer(tc)
            lowered = jitted(spec).lower(
                abstract, jax.eval_shape(opt.init, abstract), spec)
        elif shape.kind == "prefill":
            jitted, _ = ST.build_sharded_prefill(
                cfg, mesh, max_len=shape.seq, abstract_params=abstract)
            lowered = jitted(spec).lower(abstract, spec)
        else:
            jitted, _ = ST.build_sharded_serve_step(
                cfg, mesh, abstract_params=abstract,
                abstract_cache=spec["cache"], batch=shape.global_batch,
                max_len=shape.seq)
            lowered = jitted.lower(abstract, spec["cache"], spec["tokens"])
        return lowered.compile()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--min-gb", type=float, default=0.3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()

    compiled = lower_cell(args.arch, args.shape, args.multipod,
                          args.grad_accum)
    hlo = compiled.as_text()
    if args.dump:
        open(args.dump, "w").write(hlo)
    sizes = {}
    for m in re.finditer(
            r"%([\w\.\-]+) = ([a-z0-9]+)\[([0-9,]+)\]\{[^}]*\} "
            r"([\w\-\.]+)\(", hlo):
        name, dt, dims, op = m.groups()
        if dt not in DT:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        b = n * DT[dt]
        if b < args.min_gb * 1e9:
            continue
        key = (op, dt, dims)
        s = sizes.get(key, [0, 0])
        s[0] += b
        s[1] += 1
        sizes[key] = s
    for (op, dt, dims), (b, c) in sorted(sizes.items(),
                                         key=lambda kv: -kv[1][0])[:20]:
        print(f"{b/1e9:9.2f} GB  x{c:4d}  {op:24s} {dt}[{dims}]")
    mem = compiled.memory_analysis()
    print("temp GB:", mem.temp_size_in_bytes / 1e9,
          " args GB:", mem.argument_size_in_bytes / 1e9)


if __name__ == "__main__":
    main()
