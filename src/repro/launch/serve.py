"""Production serving launcher: --arch <id>, batched request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 16 --max-new 12

SNN multi-host mode (lower once per process group): point every process at
the same exported artifact and a transport — the leader lowers and
publishes, followers fetch + verify and never lower. ``--transport`` takes
``tcp://HOST:PORT`` (network, real multi-host) or a shared filesystem path
(``--program-envelope`` is the legacy spelling of the latter).

    # leader (port 0 = ephemeral; the chosen endpoint is printed)
    PYTHONPATH=src python -m repro.launch.serve \
        --snn-artifact out/mnist.npz --transport tcp://127.0.0.1:7070 \
        --role leader --await-fetches 1 --requests 32
    # follower, on any host that holds the same artifact
    PYTHONPATH=src python -m repro.launch.serve \
        --snn-artifact out/mnist.npz --transport tcp://LEADER:7070 \
        --role follower --requests 32
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config, reduced as make_reduced
from repro.models.model import LM
from repro.serving.engine import ServeEngine


def serve_snn(args) -> None:
    """The SNN leader/follower path: distribute the program, then serve."""
    from repro.core.artifact import Artifact
    from repro.core.lowering import get_cache
    from repro.launch.cluster import LeaderHandle, distribute_program
    from repro.launch.mesh import broadcast_program
    from repro.serving.snn_engine import SNNServeEngine

    art = Artifact.load(args.snn_artifact)
    transport = args.transport or args.program_envelope
    if transport:
        prog, handle = distribute_program(art, transport, role=args.role,
                                          timeout_s=args.envelope_timeout)
        if handle.endpoint is not None:
            print(f"[{args.role}] publishing program at {handle.endpoint}")
    else:
        prog = broadcast_program(art, leader=args.role == "leader")
        handle = LeaderHandle()
    engine = SNNServeEngine(art, max_batch=args.max_batch)
    rng = np.random.RandomState(0)
    images = rng.rand(args.requests, prog.n_in).astype(np.float32)
    labels = engine.classify(images)
    engine.close()
    if args.labels_out:
        np.save(args.labels_out, labels)
    if args.await_fetches > 0:
        ok = handle.await_fetches(args.await_fetches,
                                  timeout_s=args.envelope_timeout)
        state = "served" if ok else "TIMED OUT awaiting"
        print(f"[{args.role}] {state} {handle.serves}/"
              f"{args.await_fetches} follower fetch(es)")
    handle.stop()
    cs = get_cache().stats()
    print(f"[{args.role}] served {args.requests} requests; "
          f"program {prog.fingerprint[:12]}... "
          f"(cache: {cs['program_misses']} lowered, "
          f"{cs['bytes']} bytes resident)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--snn-artifact",
                    help="serve an exported SNN artifact instead of an LM")
    ap.add_argument("--program-envelope",
                    help="shared path for the serialized program envelope "
                         "(legacy spelling of --transport PATH)")
    ap.add_argument("--transport",
                    help="program distribution endpoint: tcp://HOST:PORT "
                         "or a shared filesystem path")
    ap.add_argument("--role", choices=("leader", "follower"),
                    default="leader")
    ap.add_argument("--envelope-timeout", type=float, default=30.0)
    ap.add_argument("--await-fetches", type=int, default=0,
                    help="leader: block until N followers fetched the "
                         "program before tearing the endpoint down")
    ap.add_argument("--labels-out",
                    help="save served labels to this .npy (the two-process "
                         "bit-exactness gate compares them)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.snn_artifact:
        serve_snn(args)
        return
    if not args.arch:
        ap.error("--arch is required unless --snn-artifact is given")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    lm = LM(cfg)
    params = lm.init_params(jax.random.PRNGKey(0), jnp.float32)
    engine = ServeEngine(lm, params, max_batch=args.max_batch, s_max=256)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab, rng.randint(4, 16)).astype(np.int32)
               for _ in range(args.requests)]
    outs = engine.generate(prompts, max_new=args.max_new)
    st = engine.stats()
    print(f"served {len(outs)} requests; "
          f"accelerator {st['accelerator_s']:.2f}s / "
          f"system {st['system_s']:.2f}s")


if __name__ == "__main__":
    main()
