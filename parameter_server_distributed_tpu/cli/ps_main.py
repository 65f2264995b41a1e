"""Parameter-server process entry point.

Argv contract mirrors the reference (reference: src/parameter_main.cpp:6-18):

    python -m parameter_server_distributed_tpu.cli.ps_main \
        [bind_addr] [total_workers] [checkpoint_interval] [flags...]

    bind_addr            default 0.0.0.0:50051
    total_workers        default 2
    checkpoint_interval  default 10 (iterations per checkpoint epoch)

Extension flags beyond the reference:
    --lr=F          learning rate (default 1.0, the reference's implicit lr)
    --optimizer=S   sgd | momentum | adam (host numpy/native-C++), or
                    device_{sgd,momentum,adam} (optax under jit) /
                    pallas_{sgd,momentum,adam} (fused pallas kernels) for a
                    device-resident store
    --staleness=N   bounded-staleness async mode (0 = synchronous)
    --aggregation=S streaming (default: fold-on-arrival accumulator,
                    O(model) barrier close) | buffered (classic
                    buffer-all-then-mean; also PSDT_AGGREGATION env)
    --elastic       barrier width follows live registrations (needs
                    --coordinator=ADDR to poll the registry)
    --ckpt-dir=D    checkpoint directory (default .)
    --keep=N        checkpoint retention
    --backup=ADDR   backup replica PS (replication/): the post-apply
                    store streams there after every barrier close so the
                    coordinator can promote it on this shard's death
    --replication=M async (default) | sync (close blocks on the backup
                    ack) | off — also the PSDT_REPLICATION env
    --standby=ADDR  address this PS re-arms replication toward AFTER a
                    promotion from backup to primary (otherwise the
                    promoted primary runs un-backed-up — surfaced as the
                    ps.replica.unarmed gauge in pst-status --metrics)
    --quorum=F      K-of-N barrier close (elastic/, docs/training.md
                    "Elastic membership & quorum barriers"): seal once
                    ceil(F * live width) contributors committed and the
                    grace window elapsed; stragglers fold forward
                    lr-damped.  Also the PSDT_QUORUM env; default off
                    (all-of-N, byte-identical)
    --quorum-grace-ms=N
                    grace window past the K-th commit (default 250;
                    also PSDT_QUORUM_GRACE_MS)
    --freerun       free-running barrier-free training (freerun/,
                    docs/training.md "Free-running async training"):
                    every push applies on arrival damped by
                    PSDT_STALENESS_BETA^staleness; no barrier, no seal.
                    Also the PSDT_FREERUN env; default off

With --coordinator=ADDR and PSDT_TIERS=1 the PS also polls the
coordinator's reduction topology (tiers/), so a leaf aggregator's ONE
quantized upstream push counts as its whole same-host group on the
barrier (docs/training.md "Hierarchical aggregation").
"""

from __future__ import annotations

import logging
import sys

from ..config import (DEFAULT_PS_PORT, ParameterServerConfig, parse_argv,
                      parse_host_port)
from ..server.ps_service import ParameterServer


def build_config(argv: list[str]) -> tuple[ParameterServerConfig, str | None]:
    positional, flags = parse_argv(argv)
    bind = positional[0] if len(positional) > 0 else f"0.0.0.0:{DEFAULT_PS_PORT}"
    host, port = parse_host_port(bind, DEFAULT_PS_PORT)
    config = ParameterServerConfig(
        bind_address=host, port=port,
        total_workers=int(positional[1]) if len(positional) > 1 else 2,
        checkpoint_interval=int(positional[2]) if len(positional) > 2 else 10,
        learning_rate=float(flags.get("lr", 1.0)),
        optimizer=flags.get("optimizer", "sgd"),
        staleness_bound=int(flags.get("staleness", 0)),
        aggregation=flags.get("aggregation", ""),
        elastic="elastic" in flags,
        checkpoint_dir=flags.get("ckpt-dir", "."),
        checkpoint_keep=int(flags.get("keep", 0)),
        backup_address=flags.get("backup", ""),
        replication=flags.get("replication", ""),
        standby_address=flags.get("standby", ""),
        quorum=float(flags.get("quorum", 0.0)),
        quorum_grace_ms=float(flags.get("quorum-grace-ms", -1.0)),
        freerun="freerun" in flags,
    )
    return config, flags.get("coordinator")


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    config, coordinator_addr = build_config(argv)
    if config.optimizer.partition("_")[0] in ("device", "pallas", "sharded"):
        # only a device optimizer compiles; the host PS stays jax-free
        from ..utils.compile_cache import enable_compile_cache

        enable_compile_cache()

    live_fn = None
    if config.elastic and coordinator_addr:
        # Membership-backed width provider (elastic/, ISSUE 13): counts
        # every non-GONE member and carries the membership epoch as its
        # generation, so a drain/leave/reap narrows the barrier at the
        # next width read.  Degrades internally to the classic
        # ListWorkers count against a reference coordinator.
        from ..elastic.membership import MembershipWidthProvider
        live_fn = MembershipWidthProvider(coordinator_addr)

    # Tier contribution weights ride the coordinator connection whenever
    # one is configured: the ENABLE decision lives at the coordinator
    # (the provider answers {} when tiers are off there, and latches
    # flat on UNIMPLEMENTED), so a PS host missing the PSDT_TIERS env
    # cannot silently mis-attribute group pushes under env skew.
    contributions_fn = None
    if coordinator_addr:
        from ..tiers.topology import TierContributionProvider
        contributions_fn = TierContributionProvider(coordinator_addr)

    ps = ParameterServer(config, live_workers_fn=live_fn,
                         contributions_fn=contributions_fn)
    ps.start()
    print(f"Parameter server listening on {config.bind_address}:{config.port}",
          flush=True)
    try:
        ps.wait()
    except KeyboardInterrupt:
        ps.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
