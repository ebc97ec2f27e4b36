"""ClusterServer: one bootable process = transport + coordinator + data + REST.

The Node.java:494 analog. One ClusterServer = a TcpTransport (L2), a
ClusterNode (coordinator + shards + action handlers), a LoopScheduler
(timers), and the SAME
128-route trie router the single-node server uses (rest/handlers.py),
served over a ClusterFacade that gives every handler the TpuNode API with
cluster semantics (one RestController + NodeClient in front of one action
registry, rest/RestController.java:285 + action/ActionModule.java:527).

    python -m opensearch_tpu.server --node-id n1 --port 9301 --http-port 9211 \
        --seeds n1=127.0.0.1:9301,n2=127.0.0.1:9302,n3=127.0.0.1:9303 \
        --data /tmp/c/n1 --bootstrap n1,n2,n3

HTTP handlers run on the HttpServer's executor thread and bridge onto the
transport loop through the facade; the loop itself never blocks on data
work (ClusterNode offloads engine ops to its data worker).
"""

from __future__ import annotations

import argparse
import asyncio
import json
from pathlib import Path

from opensearch_tpu.cluster.cluster_node import ClusterNode
from opensearch_tpu.cluster.facade import ClusterFacade
from opensearch_tpu.rest.http import HttpServer
from opensearch_tpu.transport.tcp import LoopScheduler, TcpTransport

REQUEST_TIMEOUT_S = 30.0


def parse_seeds(spec: str) -> dict[str, tuple[str, int]]:
    """"n1=127.0.0.1:9301,n2=..." -> {node_id: (host, port)}"""
    out: dict[str, tuple[str, int]] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        node_id, _, addr = part.partition("=")
        host, _, port = addr.rpartition(":")
        out[node_id.strip()] = (host.strip(), int(port))
    return out


class ClusterServer:
    def __init__(
        self,
        node_id: str,
        data_path: str | Path,
        transport_host: str,
        transport_port: int,
        http_port: int,
        seeds: dict[str, tuple[str, int]],
        *,
        loop: asyncio.AbstractEventLoop | None = None,
        roles: tuple[str, ...] = ("cluster_manager", "data"),
    ):
        self.loop = loop or asyncio.get_event_loop()
        self.transport = TcpTransport(
            node_id, transport_host, transport_port, seeds, loop=self.loop
        )
        self.scheduler = LoopScheduler(self.loop)
        # durable cluster state (gateway/PersistedClusterStateService:137):
        # term + accepted state survive restart; recovery happens before
        # elections so a rebooted node cannot double-vote in its old term
        from opensearch_tpu.cluster.coordination import PersistedState
        from opensearch_tpu.gateway import GatewayStore

        self.gateway = GatewayStore(Path(data_path) / "_state")
        recovered = self.gateway.load()
        if recovered is not None:
            # transient cluster settings do NOT survive a restart (the
            # persistent/transient contract of ClusterSettings.java:205)
            term, state = recovered
            persisted = PersistedState(
                term, state.with_(transient_settings={}), store=self.gateway
            )
        else:
            persisted = PersistedState(store=self.gateway)
        self.node = ClusterNode(
            node_id, data_path, self.transport, self.scheduler,
            peers=[p for p in seeds if p != node_id], roles=roles,
            persisted=persisted,
        )
        self.facade = ClusterFacade(self.node, self.loop)
        self.http = HttpServer(self.facade, transport_host, http_port)
        self.http_host = transport_host
        self.http_port = http_port

    async def start(self, bootstrap: list[str] | None = None) -> None:
        await self.transport.start()
        self.node.start()
        if bootstrap:
            self.node.bootstrap(bootstrap)
        await self.http.start()

    async def aclose(self) -> None:
        await self.http.stop()
        self.node.close()
        await self.transport.aclose()


async def amain(args: argparse.Namespace) -> None:
    from opensearch_tpu.bootstrap import (
        configure_compile_cache,
        startup_report,
    )

    report = startup_report(configure_compile_cache())
    seeds = parse_seeds(args.seeds)
    server = ClusterServer(
        args.node_id, args.data, args.host,
        seeds[args.node_id][1], args.http_port, seeds,
        loop=asyncio.get_running_loop(),
    )
    bootstrap = args.bootstrap.split(",") if args.bootstrap else None
    await server.start(bootstrap=bootstrap)
    print(f"[{args.node_id}] transport {seeds[args.node_id]} "
          f"http 127.0.0.1:{args.http_port} "
          f"started={json.dumps(report)}", flush=True)
    await asyncio.Event().wait()  # run forever


def main() -> None:
    parser = argparse.ArgumentParser(description="opensearch-tpu cluster node")
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--http-port", type=int, required=True)
    parser.add_argument("--data", required=True)
    parser.add_argument("--seeds", required=True,
                        help="n1=127.0.0.1:9301,n2=127.0.0.1:9302,...")
    parser.add_argument("--bootstrap", default=None,
                        help="comma-separated voting node ids (first boot)")
    args = parser.parse_args()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
