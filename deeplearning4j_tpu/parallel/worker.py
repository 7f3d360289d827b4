"""Worker-process entry point for distributed training.

The process shape of the reference's Spark executor running
``ExecuteWorkerFlatMap`` (SURVEY §3.3 step "mapPartitions"): one OS process per
worker — on a real cluster, one per host — that connects to the coordinator,
receives broadcast (config, params, updater state), streams its Export-mode
data shard from disk, fits, and allreduces results back.

Usage (spawned by ParameterAveragingTrainingMaster in mode='process', or
launched manually on each host):

    python -m deeplearning4j_tpu.parallel.worker \
        --host <coordinator-host> --port <port> --worker-id <i> \
        --data-dir <export_dir>/worker_<i>
"""

from __future__ import annotations

import argparse
import glob
import os


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--host", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--worker-id", type=int, required=True)
    parser.add_argument("--data-dir", required=True)
    parser.add_argument("--no-native", action="store_true",
                        help="force the pure-Python collective client")
    args = parser.parse_args(argv)

    from deeplearning4j_tpu.parallel.coordinator import connect
    from deeplearning4j_tpu.parallel.training_master import (load_dataset,
                                                             run_worker_loop)

    def data_source(split_idx, meta):
        d = os.path.join(args.data_dir, f"split_{split_idx}")
        return [load_dataset(p)
                for p in sorted(glob.glob(os.path.join(d, "batch_*.npz")))]

    client = connect(args.host, args.port, args.worker_id,
                     prefer_native=not args.no_native)
    try:
        run_worker_loop(client, data_source)
    finally:
        client.close()


if __name__ == "__main__":
    main()
