"""The system under test, built the way a deployment builds it: seeded numpy
columns -> build_segment -> Coordinator -> ServerInstance(device=chip) with
every column staged in HBM -> Broker -> QueryServer on a loopback port.
The only file of the benchmark that imports pinot_tpu."""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

import pinot_tpu  # noqa: F401  (sets x64 and the compile cache's fixed directory before JAX starts)
from lib import plugins

_WIDE = {"INT": np.int32, "LONG": np.int64}


class Cluster:
    """Owns the servers, the broker and the HTTP front door; `close()` stops
    the front door and hands the device memory back."""

    def __init__(self, config: Dict[str, Any], seed: int, devices: List[Any], build_threads: int = 4):
        import jax

        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.coordinator import Coordinator
        from pinot_tpu.cluster.rest import QueryServer
        from pinot_tpu.cluster.server import ServerInstance
        from pinot_tpu.segment.builder import build_segment
        from pinot_tpu.spi.config import IndexingConfig, TableConfig
        from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema

        self.config = config
        table = config["table"]
        schema = Schema(
            table,
            [
                FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]])
                for c in config["columns"]
            ],
        )
        tcfg = TableConfig(table, indexing=IndexingConfig.from_dict(config["table_config"]))
        gen = plugins.load_module("datagen", config["datagen"])
        rows, seg_rows = int(config["rows"]), int(config["segment_rows"])
        self.num_segments = -(-rows // seg_rows)
        self.blocks: List[Dict[str, np.ndarray]] = [None] * self.num_segments  # the reference's rows
        self.timers = {"datagen_s": 0.0, "segment_build_s": 0.0, "stage_s": 0.0}

        self.coordinator = Coordinator(replication=int(config["replication"]))
        self.servers = [
            ServerInstance(f"server{i}", device=d) for i, d in enumerate(devices[: int(config["servers"])])
        ]
        for s in self.servers:
            self.coordinator.register_server(s)
        self.coordinator.add_table(schema, tcfg)

        def make(i: int):
            t0 = time.perf_counter()
            n = min(seg_rows, rows - i * seg_rows)
            block = gen.make_segment(config, seed, i, n)
            t1 = time.perf_counter()
            wide = {c["name"]: block[c["name"]].astype(_WIDE[c["type"]]) for c in config["columns"]}
            seg = build_segment(schema, wide, f"seg{i}", table_config=tcfg)
            return i, block, seg, t1 - t0, time.perf_counter() - t1

        t_all = time.perf_counter()
        # host threads: numpy's sorts and uniques release the interpreter lock
        with ThreadPoolExecutor(max_workers=build_threads) as pool:
            for i, block, seg, gen_s, build_s in pool.map(make, range(self.num_segments)):
                self.blocks[i] = block
                self.timers["datagen_s"] += gen_s
                self.timers["segment_build_s"] += build_s
                self.coordinator.add_segment(table, seg)
        self.timers["build_wall_s"] = time.perf_counter() - t_all

        t0 = time.perf_counter()
        self.bytes_staged = 0
        for s in self.servers:
            for seg in s.segments.get(table, {}).values():
                tree = seg.to_device(
                    device=s.device, packed_codes=bool(config["packed_codes"]), residency=s.residency
                )
                leaves = jax.tree_util.tree_leaves(tree)
                jax.block_until_ready(leaves)
                self.bytes_staged += sum(int(leaf.nbytes) for leaf in leaves)
        self.timers["stage_s"] = time.perf_counter() - t0

        self.broker = Broker(self.coordinator)
        self.front = QueryServer(self.broker).start()
        self.url = f"127.0.0.1:{self.front.port}"

    def counters(self) -> Dict[str, float]:
        """The program's counters and timers as it exports them: the
        process-wide registry plus each server's own."""
        from pinot_tpu.utils.metrics import METRICS

        out: Dict[str, float] = {}
        for snap in [METRICS.snapshot()] + [s.metrics.snapshot() for s in self.servers]:
            for k, v in snap["counters"].items():
                out[k] = out.get(k, 0.0) + float(v)
            for k, t in snap["timers"].items():
                out[f"timer:{k}:count"] = out.get(f"timer:{k}:count", 0.0) + float(t["count"])
                out[f"timer:{k}:total_ms"] = out.get(f"timer:{k}:total_ms", 0.0) + float(t["count"] * t["meanMs"])
        return out

    def close(self) -> None:
        self.front.stop()
        table = self.config["table"]
        for s in self.servers:
            for name in list(s.segment_names(table)):
                s.drop_segment(table, name)
