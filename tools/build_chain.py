#!/usr/bin/env python3
"""build_chain — generate an N-node chain deployment directory.

Counterpart of the reference's tools/BcosAirBuilder/build_chain.sh (generate
an N-node Air chain: keys, per-node config.ini, shared genesis) and the
BcosBuilder Pro/Max deployers. Output layout:

    <out>/
      node0/ config.ini  genesis  node.key[.enc]
      node1/ ...
      chain_info.json          (node ids + rpc ports, for operators/SDKs)

Usage:
    python tools/build_chain.py -n 4 -o /tmp/mychain [--sm] \
        [--consensus pbft] [--rpc-base-port 20200] [--encrypt-key PASS] \
        [--crypto-backend auto,host,host,host] \
        [--block-tx-count-limit 1000]

Boot a generated node in-process:
    from fisco_bcos_tpu.tool import load_node
    node = load_node("/tmp/mychain/node0", gateway=...)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fisco_bcos_tpu.crypto.suite import make_suite  # noqa: E402
from fisco_bcos_tpu.init.node import NodeConfig  # noqa: E402
from fisco_bcos_tpu.tool.config import ChainConfig, save_node_config  # noqa: E402


def _write_monitor_stack(out_dir: str, targets: list[str]) -> None:
    """Copy the monitor bundle (tools/monitor) into the chain dir with the
    Prometheus target list rewritten to the generated nodes' ports."""
    import shutil

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "monitor")
    dst = os.path.join(out_dir, "monitor")
    shutil.copytree(src, dst, dirs_exist_ok=True)
    lines = ["global:", "  scrape_interval: 5s", "", "scrape_configs:",
             "  - job_name: fisco-bcos-tpu", "    static_configs:",
             "      - targets:"]
    lines += [f'          - "{t}"' for t in targets]
    with open(os.path.join(dst, "prometheus.yml"), "w") as f:
        f.write("\n".join(lines) + "\n")


def build_chain(out_dir: str, n_nodes: int, sm_crypto: bool = False,
                consensus: str = "pbft", chain_id: str = "chain0",
                group_id: str = "group0", rpc_base_port: int | None = None,
                encrypt_passphrase: bytes | None = None,
                crypto_backend: str = "auto",
                storage_backend: str = "auto",
                metrics_base_port: int | None = None,
                sm_tls: bool = False,
                p2p_base_port: int | None = None,
                p2p_ports: list[int] | None = None,
                host: str = "127.0.0.1",
                block_tx_count_limit: int = 1000,
                key_page_size: int = -1) -> dict:
    if block_tx_count_limit < 1:
        raise ValueError("block_tx_count_limit must be >= 1")
    if 0 < key_page_size < 4096:
        raise ValueError("key_page_size must be 0 (off), auto (-1) or at "
                         "least 4096 bytes, as upstream's")
    # one value for every node, or one per node ("auto,host,host,host"): a
    # chip belongs to one process at a time, so on a one-chip host exactly
    # one daemon may be anything but `host`
    backends = crypto_backend.split(",")
    if len(backends) == 1:
        backends *= n_nodes
    if len(backends) != n_nodes or \
            any(b not in ("auto", "host", "device") for b in backends):
        raise ValueError(
            f"crypto backend {crypto_backend!r}: want auto|host|device, "
            f"once or once per node ({n_nodes})")
    suite = make_suite(sm_crypto, backend="host")
    keypairs = [suite.generate_keypair() for _ in range(n_nodes)]
    chain = ChainConfig(chain_id=chain_id, group_id=group_id,
                        sm_crypto=sm_crypto, consensus_type=consensus,
                        block_tx_count_limit=block_tx_count_limit,
                        sealers=[kp.pub_bytes for kp in keypairs])
    ca = None
    if sm_tls:
        from fisco_bcos_tpu.net.smtls import CertificateAuthority
        from fisco_bcos_tpu.tool.config import save_smtls_files
        ca = CertificateAuthority(name=f"{chain_id}-ca")
    info = {"chain_id": chain_id, "group_id": group_id,
            "sm_crypto": sm_crypto, "sm_tls": sm_tls,
            "consensus": consensus, "nodes": []}
    # p2p plane: each node listens on its port and is configured with every
    # OTHER node's endpoint (the deterministic smaller-id-dials rule in
    # net/p2p.py picks the single live session per pair)
    if p2p_ports is None and p2p_base_port is not None:
        p2p_ports = [p2p_base_port + i for i in range(n_nodes)]
    metric_targets = []
    for i, kp in enumerate(keypairs):
        node_dir = os.path.join(out_dir, f"node{i}")
        cfg = NodeConfig(
            chain_id=chain_id, group_id=group_id, sm_crypto=sm_crypto,
            storage_path="data", consensus=consensus,
            # config.ini repeats what genesis says; genesis is what a
            # node obeys (tool/config.py _load_node_parts)
            tx_count_limit=block_tx_count_limit,
            storage_backend=storage_backend,
            storage_key_page_size=key_page_size,
            crypto_backend=backends[i],
            rpc_port=(rpc_base_port + i) if rpc_base_port is not None else None,
            metrics_port=(metrics_base_port + i)
            if metrics_base_port is not None else None,
            p2p_host=host,
            p2p_port=p2p_ports[i] if p2p_ports else None,
            p2p_peers=[(host, p) for j, p in enumerate(p2p_ports or [])
                       if j != i],
        )
        save_node_config(node_dir, cfg, chain, kp.secret,
                         storage_passphrase=encrypt_passphrase)
        if ca is not None:
            save_smtls_files(node_dir, ca.pub, ca.issue(f"node{i}"),
                             storage_passphrase=encrypt_passphrase)
        if cfg.metrics_port is not None:
            metric_targets.append(f"127.0.0.1:{cfg.metrics_port}")
        info["nodes"].append({
            "dir": node_dir,
            "node_id": kp.pub_bytes.hex(),
            "rpc_port": cfg.rpc_port,
            "metrics_port": cfg.metrics_port,
            "p2p_port": cfg.p2p_port,
            "crypto_backend": cfg.crypto_backend,
        })
    if metric_targets:
        _write_monitor_stack(out_dir, metric_targets)
    with open(os.path.join(out_dir, "chain_info.json"), "w") as f:
        json.dump(info, f, indent=2)
    return info


def build_max_cluster(out_dir: str, n_shards: int = 3,
                      n_registries: int = 3,
                      shard_base_port: int = 21100,
                      registry_base_port: int = 21200,
                      host: str = "127.0.0.1") -> dict:
    """Generate the Max-mode shared-services layout: a sharded storage
    cluster + lease registries (the TiKV + etcd plane). Boot each member
    with fisco_bcos_tpu.services.max_node.start_storage_shard /
    start_lease_registry, and node replicas with MaxNode against
    max_cluster.json's endpoints."""
    shards, registries = [], []
    for i in range(n_shards):
        d = os.path.join(out_dir, "shards", f"shard{i}")
        os.makedirs(d, exist_ok=True)
        shards.append({"dir": d, "host": host,
                       "port": shard_base_port + i})
    regs_dir = os.path.join(out_dir, "registries")
    os.makedirs(regs_dir, exist_ok=True)
    for i in range(n_registries):
        registries.append({"state": os.path.join(regs_dir, f"reg{i}.json"),
                           "host": host, "port": registry_base_port + i})
    cluster = {"shards": shards, "registries": registries}
    with open(os.path.join(out_dir, "max_cluster.json"), "w") as f:
        json.dump(cluster, f, indent=2)
    return cluster


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--nodes", type=int, default=4)
    ap.add_argument("-o", "--output", required=True)
    ap.add_argument("--sm", action="store_true", help="SM2/SM3 chain")
    ap.add_argument("--consensus", default="pbft", choices=["pbft", "solo"])
    ap.add_argument("--chain-id", default="chain0")
    ap.add_argument("--group-id", default="group0")
    ap.add_argument("--rpc-base-port", type=int, default=None)
    ap.add_argument("--p2p-base-port", type=int, default=None,
                    help="per-node TCP p2p listeners + full-mesh peer "
                         "lists (required to run nodes as OS processes)")
    ap.add_argument("--metrics-base-port", type=int, default=None,
                    help="per-node Prometheus ports + monitor stack bundle")
    ap.add_argument("--sm-tls", action="store_true",
                    help="issue dual-cert SM-TLS credentials per node")
    ap.add_argument("--storage", default="auto",
                    choices=["auto", "memory", "wal", "disk"],
                    help="[storage] backend: auto = WAL-backed; disk = "
                         "log-structured engine (restart flat in chain "
                         "length, datasets beyond RAM)")
    ap.add_argument("--key-page-size", type=int, default=-1,
                    help="[storage] key_page_size in every node's "
                         "config.ini: the bytes at which a page of rows "
                         "splits (upstream's build_chain.sh writes 10240). "
                         "Default: `auto` = 10240 on disk, no paging on "
                         "wal/memory; 0 turns paging off")
    ap.add_argument("--crypto-backend", default="auto",
                    help="[crypto] backend: auto|host|device, one value "
                         "for all nodes or a comma list, one per node "
                         "(one chip serves one process: on a one-chip "
                         "host give node0 the chip and the rest `host`)")
    ap.add_argument("--block-tx-count-limit", type=int, default=1000,
                    help="genesis [consensus] block_tx_count_limit: the "
                         "most transactions a block may hold (the ledger's "
                         "system config tx_count_limit). The ingest lane's "
                         "batch and queue sizes and the crypto shapes a "
                         "device node compiles follow from it; keep it "
                         "under [txpool] limit")
    ap.add_argument("--encrypt-key", default=None,
                    help="passphrase to encrypt node keys at rest")
    ap.add_argument("--mode", default="air", choices=["air", "max"],
                    help="max adds the shared shard cluster + lease "
                         "registries layout (max_cluster.json)")
    ap.add_argument("--shards", type=int, default=3)
    ap.add_argument("--registries", type=int, default=3)
    args = ap.parse_args()
    info = build_chain(
        args.output, args.nodes, sm_crypto=args.sm,
        consensus=args.consensus, chain_id=args.chain_id,
        group_id=args.group_id, rpc_base_port=args.rpc_base_port,
        p2p_base_port=args.p2p_base_port,
        metrics_base_port=args.metrics_base_port, sm_tls=args.sm_tls,
        storage_backend=args.storage, crypto_backend=args.crypto_backend,
        block_tx_count_limit=args.block_tx_count_limit,
        key_page_size=args.key_page_size,
        encrypt_passphrase=args.encrypt_key.encode() if args.encrypt_key else None)
    if args.mode == "max":
        info["max_cluster"] = build_max_cluster(
            args.output, n_shards=args.shards,
            n_registries=args.registries)
    print(json.dumps(info, indent=2))


if __name__ == "__main__":
    main()
