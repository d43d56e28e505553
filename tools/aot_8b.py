"""AOT-compile a Llama train step (any preset, --model) against a
detached TPU topology (VERDICT r3 weak #4 / next-round item 3).

JAX's AOT path (`jax.experimental.topologies.get_topology_desc` +
`jit(...).lower(...).compile()`) runs the REAL XLA:TPU compiler against a
TopologyDescription without any attached device, so the per-chip HBM plan
in docs/SCALING.md can be validated by the compiler instead of
arithmetic. Prints one JSON summary and writes tools/aot_8b_result.json.

Usage (CPU host, no TPU needed):
    JAX_PLATFORMS=cpu python tools/aot_8b.py [--mesh fsdp=16]
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GiB = 1024 ** 3
# SCALING.md "Recommended configuration": batch 16 x seq 8192 on
# fsdp=16 over a v5p-32 slice (16 chips, 95 GB HBM each)
BATCH, SEQ = 16, 8192
TOPOLOGY = "v5p:2x2x4"
HBM_GIB = {"v5p": 95.0, "v5e": 16.0, "v5lite": 16.0, "v4": 32.0}


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="AOT-compile a Llama train step (--model preset) "
                    "against a detached TPU topology")
    parser.add_argument("--mesh", default="fsdp:16",
                        help="axis:size list, e.g. fsdp:8,tp:2 or "
                             "pp:4,fsdp:4")
    parser.add_argument("--topology", default=TOPOLOGY)
    parser.add_argument("--slices", type=int, default=1,
                        help=">1 compiles a multi-slice hybrid mesh "
                             "(outermost axes cross DCN)")
    parser.add_argument("--batch", type=int, default=BATCH)
    parser.add_argument("--seq", type=int, default=SEQ)
    parser.add_argument("--generate", action="store_true",
                        help="compile the inference path (prefill + "
                             "KV-cache decode scan) instead of the "
                             "train step")
    parser.add_argument("--virtual", type=int, default=1,
                        help="virtual chunks per pipeline stage (pp "
                             "meshes; >1 = interleaved schedule)")
    parser.add_argument("--model", default="llama3_8b",
                        help="LlamaConfig preset, or a MoEConfig preset "
                             "(moe_tiny / mixtral_proxy) for the "
                             "expert-parallel path")
    args = parser.parse_args()
    mesh_kwargs = {}
    for part in args.mesh.split(","):
        k, _, v = part.partition(":")
        mesh_kwargs[k.strip()] = int(v)
    if args.generate and (args.virtual > 1
                          or mesh_kwargs.get("pp", 1) > 1):
        # argv-detectable conflict: fail before any topology/mesh work
        raise SystemExit(
            "--generate compiles the inference path only; --virtual "
            "and pp meshes apply to the train step — drop them or "
            "drop --generate")
    topology, num_slices = args.topology, args.slices
    batch, seq = args.batch, args.seq
    # strict lookup: an unknown device generation must not inherit the
    # largest part's HBM and fake a fits=true verdict
    hbm_gib = next((v for k, v in HBM_GIB.items()
                    if topology.lower().startswith(k)), None)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from tony_tpu.models.llama import (
        get_config, llama_init, llama_loss, llama_param_axes,
    )
    from tony_tpu.parallel.mesh import make_mesh, plan_mesh
    from tony_tpu.parallel.sharding import (
        logical_to_mesh_axes, make_partition_spec,
    )
    from tony_tpu.train.precision import with_f32_master
    from tony_tpu.train.step import make_train_step

    t0 = time.monotonic()
    kw = {"num_slices": num_slices} if num_slices > 1 else {}
    topo = topologies.get_topology_desc(topology, "tpu", **kw)
    if num_slices > 1:
        # DCN-crossing layout: outermost plan axes span slices, inner
        # axes stay within a slice on ICI (the scaling-book rule the
        # hybrid mesh implements)
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        mesh = make_hybrid_mesh(plan_mesh(len(topo.devices),
                                          **mesh_kwargs), topo.devices)
    else:
        mesh = make_mesh(plan_mesh(len(topo.devices), **mesh_kwargs),
                         topo.devices)
    print(f"[aot] topology {topology} x{num_slices}: "
          f"{len(topo.devices)} chips, mesh {dict(mesh.shape)}",
          file=sys.stderr)

    from tony_tpu.models.moe import is_moe_preset
    is_moe = is_moe_preset(args.model)
    if is_moe:
        from tony_tpu.models.moe import (
            get_moe_config, moe_init, moe_loss, moe_param_axes,
        )
        config = get_moe_config(args.model)
        init_fn = partial(moe_init, config)
        param_axes = moe_param_axes(config)
    else:
        config = get_config(args.model)
        init_fn = partial(llama_init, config)
        param_axes = llama_param_axes(config)

    def sds(tree, spec_tree=None):
        """eval_shape tree -> ShapeDtypeStructs with shardings."""
        def one(leaf, spec=None):
            sharding = NamedSharding(
                mesh, spec if spec is not None else jax.P())
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=sharding)
        if spec_tree is None:
            return jax.tree.map(one, tree)
        return jax.tree.map(one, tree, spec_tree)

    abstract_params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    param_specs = make_partition_spec(param_axes, mesh=mesh)
    params_in = sds(abstract_params, param_specs)

    if args.generate:
        # inference path: --seq is the PROMPT length (prefill), 64 new
        # tokens decoded through the KV-cache scan
        if is_moe:
            raise SystemExit("--generate supports the Llama presets only")
        from tony_tpu.models.generate import generate
        prompt_in = jax.ShapeDtypeStruct(
            (batch, seq), jnp.int32,
            sharding=NamedSharding(
                mesh, logical_to_mesh_axes(("batch",), mesh=mesh)))
        print("[aot] lowering + compiling generate (prefill + KV-cache "
              "decode scan)...", file=sys.stderr)
        with jax.set_mesh(mesh):
            exe = jax.jit(
                lambda p, t: generate(p, config, t, 64)).lower(
                    params_in, prompt_in).compile()
    else:
        exe = None
    # train-step construction only when the train step is what compiles:
    # in --generate mode the full-scale optimizer eval_shape + loss/step
    # build was pure wasted compile-path work (r4 advisor)
    if exe is None:
        optimizer = with_f32_master(optax.adamw(3e-4))
        with jax.set_mesh(mesh):
            # explicit optimizer-state specs (masters/moments mirror the
            # param tree): propagation left the Adam moments replicated on
            # this very compile before opt_state_specs existed
            from tony_tpu.parallel.sharding import opt_state_specs
            opt_shapes = jax.eval_shape(optimizer.init, params_in)
            opt_in = jax.tree.map(
                lambda s, spec: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
                opt_shapes, opt_state_specs(opt_shapes, param_specs))

            batch_spec = logical_to_mesh_axes(("batch", "seq"), mesh=mesh)
            if is_moe:
                # MoE batches ship as {'tokens': (B, S+1)}; seq+1 must stay
                # divisible enough for the sp spec -> keep tokens unsharded
                # on seq (moe runs ep/fsdp meshes)
                tok_spec = logical_to_mesh_axes(("batch",), mesh=mesh)
                batch_in = {"tokens": jax.ShapeDtypeStruct(
                    (batch, seq + 1), jnp.int32,
                    sharding=NamedSharding(mesh, tok_spec))}
            else:
                batch_in = {
                    "inputs": jax.ShapeDtypeStruct(
                        (batch, seq), jnp.int32,
                        sharding=NamedSharding(mesh, batch_spec)),
                    "targets": jax.ShapeDtypeStruct(
                        (batch, seq), jnp.int32,
                        sharding=NamedSharding(mesh, batch_spec)),
                }
            if is_moe:
                if mesh_kwargs.get("pp", 1) > 1:
                    raise SystemExit(
                        "MoE has no pipelined loss — a pp axis would record "
                        "a mesh the compiled program never uses")
                loss_fn = partial(moe_loss, config=config)
            elif mesh_kwargs.get("pp", 1) > 1:
                # pipeline-parallel compile check: the pp path (1F1B custom
                # backward, blockwise attention inside the manual stage,
                # interleaved when --virtual > 1) had only ever lowered for
                # CPU before this
                from tony_tpu.models.llama import llama_loss_pipelined
                loss_fn = partial(llama_loss_pipelined, config=config,
                                  mesh=mesh, n_micro=4,
                                  n_virtual=args.virtual)
            else:
                loss_fn = partial(llama_loss, config=config)
            step = make_train_step(loss_fn, optimizer, jit=False,
                                   emit_accum_dtype=True)
            print("[aot] lowering + compiling the full train step "
                  "(fwd+bwd+adamw, donated state)...", file=sys.stderr)
            exe = jax.jit(
                step, donate_argnums=(0, 1)).lower(
                    params_in, opt_in, batch_in).compile()

    mem = exe.memory_analysis()
    result = {
        "topology": topology,
        "num_slices": num_slices,
        "mesh": dict(mesh.shape),
        "model": args.model,
        **({"mode": "generate"} if args.generate else {}),
        **({"n_virtual": args.virtual} if args.virtual > 1 else {}),
        "batch": batch, "seq": seq,
        "compile_s": round(time.monotonic() - t0, 1),
    }
    if mem is not None:
        per_chip = {
            "argument_gib": round(mem.argument_size_in_bytes / GiB, 2),
            "output_gib": round(mem.output_size_in_bytes / GiB, 2),
            "temp_gib": round(mem.temp_size_in_bytes / GiB, 2),
            "peak_total_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                / GiB, 2),
            "hbm_per_chip_gib": hbm_gib,
        }
        per_chip["fits"] = (per_chip["peak_total_gib"] < hbm_gib
                            if hbm_gib is not None else None)
        result["memory_analysis_per_chip"] = per_chip
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "aot_8b_result.json")
    # the key must capture EVERY knob that changes the numbers, or a
    # sweep overwrites the canonical rows SCALING.md cites
    key = "x".join(f"{k}{v}" for k, v in sorted(mesh_kwargs.items()))
    if topology != TOPOLOGY or num_slices > 1:
        key += f"-{topology}-s{num_slices}"
    if (batch, seq) != (BATCH, SEQ):
        key += f"-b{batch}-s{seq}"
    if args.model != "llama3_8b":
        key += f"-{args.model}"
    if args.virtual > 1:
        key += f"-v{args.virtual}"
    if args.generate:
        key += "-generate"
    try:
        with open(out_path, "r", encoding="utf-8") as f:
            all_results = json.load(f)
        if "mesh" in all_results:   # pre-dict format
            all_results = {}
    except (OSError, ValueError):
        all_results = {}
    all_results[key] = result
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(all_results, f, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
