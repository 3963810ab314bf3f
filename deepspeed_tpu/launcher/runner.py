"""Multi-host job front-end — the ``deepspeed`` CLI for TPU pods.

Capability parity with reference ``launcher/runner.py:254`` (hostfile
parsing, ``--include/--exclude`` resource filters, coordinator resolution,
world-info encoding, backend dispatch), re-targeted at the TPU process
model: JAX owns every chip on a host from ONE process, so the runner spawns
one worker process per host (times ``--procs_per_node`` for megacore /
CPU-simulation runs), not one per device. Slot filtering maps to chip
visibility (``TPU_VISIBLE_CHIPS``) instead of ``CUDA_VISIBLE_DEVICES``.

Topology sources, in priority order:
1. ``--hostfile`` in MPI style (``worker-0 slots=4``) — reference format;
2. ``--tpu_pod`` : ask the local TPU metadata for pod worker hostnames
   (gated: requires a TPU VM environment);
3. localhost fallback (single host, all local chips).
"""
from __future__ import annotations

import argparse
import base64
import collections
import json
import os
import shutil
import subprocess
import sys
from copy import deepcopy
from typing import Dict, List, Optional

from .constants import (DEEPSPEED_ENVIRONMENT_NAME, DEFAULT_COORDINATOR_PORT,
                        DEFAULT_HOSTFILE, EXPORT_ENV_PREFIXES, GCLOUD_LAUNCHER,
                        PDSH_LAUNCHER,
                        SSH_LAUNCHER)
from ..utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="deepspeed_tpu runner: launch multi-host TPU training")
    parser.add_argument("-H", "--hostfile", type=str, default=DEFAULT_HOSTFILE,
                        help="MPI-style hostfile: '<host> slots=<chips>' per line")
    parser.add_argument("-i", "--include", type=str, default="",
                        help="NODE_SPEC[@NODE_SPEC ...] with "
                             "NODE_SPEC=NAME[:SLOT[,SLOT ...]]")
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help="same syntax as --include; mutually exclusive")
    parser.add_argument("--num_nodes", type=int, default=-1,
                        help="use only the first N hosts of the pool")
    parser.add_argument("--num_chips", "--num_gpus", dest="num_chips",
                        type=int, default=-1,
                        help="use chips [0:N) on every host")
    parser.add_argument("--coordinator_port", "--master_port",
                        dest="coordinator_port", type=int,
                        default=DEFAULT_COORDINATOR_PORT)
    parser.add_argument("--coordinator_addr", "--master_addr",
                        dest="coordinator_addr", type=str, default="")
    parser.add_argument("--launcher", type=str, default=PDSH_LAUNCHER,
                        help=f"{PDSH_LAUNCHER} | {SSH_LAUNCHER} | "
                             f"{GCLOUD_LAUNCHER}")
    parser.add_argument("--tpu_name", type=str, default=None,
                        help="Cloud TPU pod slice name (gcloud launcher)")
    parser.add_argument("--tpu_zone", type=str, default=None,
                        help="Cloud TPU zone (gcloud launcher)")
    parser.add_argument("--launcher_args", type=str, default="")
    parser.add_argument("--procs_per_node", type=int, default=1,
                        help="worker processes per host (1 for TPU: JAX owns "
                             "all local chips; >1 for CPU simulation)")
    parser.add_argument("--tpu_pod", action="store_true",
                        help="discover hosts from TPU pod metadata")
    parser.add_argument("--force_multi", action="store_true",
                        help="treat a 1-host pool as multi-node (ssh path)")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def fetch_hostfile(hostfile_path: str) -> Optional["collections.OrderedDict"]:
    """Parse ``<host> slots=<n>`` lines (reference runner.py:115-142)."""
    if not os.path.isfile(hostfile_path):
        logger.warning("Unable to find hostfile, proceeding with local "
                       "resources only.")
        return None
    resource_pool: "collections.OrderedDict[str, int]" = collections.OrderedDict()
    with open(hostfile_path) as fd:
        for line in fd:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                hostname, slots = line.split()
                _, slot_count = slots.split("=")
                slot_count = int(slot_count)
            except ValueError as err:
                logger.error("Hostfile is not formatted correctly")
                raise err
            if hostname in resource_pool:
                raise ValueError(f"host {hostname} is already defined")
            resource_pool[hostname] = slot_count
    return resource_pool


def discover_tpu_pod() -> Optional["collections.OrderedDict"]:
    """TPU pod topology from instance metadata (one entry per worker host).

    On Cloud TPU VMs the pod's worker list is exposed via the metadata
    server / ``TPU_WORKER_HOSTNAMES`` env. Gated: returns None when neither
    is available (dev boxes, CI).
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    chips = int(os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS", "0") or 0)
    if not hostnames:
        return None
    pool: "collections.OrderedDict[str, int]" = collections.OrderedDict()
    for h in hostnames.split(","):
        h = h.strip()
        if h:
            pool[h] = chips if chips > 0 else 4
    return pool


def parse_resource_filter(host_info: Dict[str, List[int]], include_str="",
                          exclude_str="") -> "collections.OrderedDict":
    """Filter ``{host: [slot, ...]}`` by NODE_SPEC strings.

    Same syntax and semantics as reference runner.py:146-231:
    ``worker-0@worker-1:0,2`` keeps all of worker-0 and slots 0,2 of
    worker-1; exclusion removes listed slots (a bare hostname excludes the
    whole host). Include and exclude are mutually exclusive.
    """
    NODE_SEP, SLOT_LIST_START, SLOT_SEP = "@", ":", ","

    if include_str and exclude_str:
        raise ValueError("include_str and exclude_str are mutually exclusive.")
    if not include_str and not exclude_str:
        return collections.OrderedDict(host_info)

    filtered_hosts: Dict[str, List[int]] = {}
    if include_str:
        parse_str = include_str
    else:
        filtered_hosts = deepcopy(dict(host_info))
        parse_str = exclude_str

    for node_config in parse_str.split(NODE_SEP):
        if SLOT_LIST_START in node_config:
            hostname, slots = node_config.split(SLOT_LIST_START)
            slot_ids = [int(x) for x in slots.split(SLOT_SEP)]
            if hostname not in host_info:
                raise ValueError(f"Hostname '{hostname}' not found in hostfile")
            for s in slot_ids:
                if s not in host_info[hostname]:
                    raise ValueError(
                        f"No slot '{s}' specified on host '{hostname}'")
            if include_str:
                filtered_hosts[hostname] = slot_ids
            else:
                for s in slot_ids:
                    filtered_hosts[hostname].remove(s)
        else:
            hostname = node_config
            if hostname not in host_info:
                raise ValueError(f"Hostname '{hostname}' not found in hostfile")
            if include_str:
                filtered_hosts[hostname] = list(host_info[hostname])
            else:
                filtered_hosts[hostname] = []

    # dedup slots, drop empty hosts, restore hostfile ordering
    ordered = collections.OrderedDict()
    for host in host_info:
        if host in filtered_hosts and filtered_hosts[host]:
            ordered[host] = sorted(set(filtered_hosts[host]))
    return ordered


def parse_inclusion_exclusion(resource_pool: Dict[str, int], inclusion: str,
                              exclusion: str) -> "collections.OrderedDict":
    active = collections.OrderedDict(
        (host, list(range(slots))) for host, slots in resource_pool.items())
    return parse_resource_filter(active, include_str=inclusion,
                                 exclude_str=exclusion)


def encode_world_info(world_info: Dict[str, List[int]]) -> str:
    return base64.urlsafe_b64encode(
        json.dumps(world_info).encode("utf-8")).decode("utf-8")


def decode_world_info(world_info_base64: str) -> Dict[str, List[int]]:
    return json.loads(base64.urlsafe_b64decode(world_info_base64))


def _resolve_coordinator(active_resources, args) -> str:
    if args.coordinator_addr:
        return args.coordinator_addr
    if getattr(args, "launcher", "").lower() == GCLOUD_LAUNCHER:
        # No direct ssh route to managed pod workers (that is the whole
        # point of the gcloud wrapper): defer resolution to the workers,
        # which read the coordinator from TPU_WORKER_HOSTNAMES.
        return "@pod-coordinator"
    first_host = next(iter(active_resources))
    if first_host in ("localhost", "127.0.0.1"):
        return "127.0.0.1"
    out = subprocess.check_output([f"ssh {first_host} hostname -I"], shell=True)
    addr = out.decode("utf-8").split()[0]
    logger.info(f"Using IP address of {addr} for node {first_host}")
    return addr


def _collect_exports(env) -> Dict[str, str]:
    exports = {}
    for var, val in env.items():
        if any(var.startswith(p) for p in EXPORT_ENV_PREFIXES):
            exports[var] = val
    for environ_path in [os.path.expanduser("~"), "."]:
        environ_file = os.path.join(environ_path, DEEPSPEED_ENVIRONMENT_NAME)
        if os.path.isfile(environ_file):
            with open(environ_file) as fd:
                for line in fd:
                    line = line.strip()
                    if line and "=" in line:
                        key, val = line.split("=", 1)
                        exports[key] = val
    return exports


def local_chip_count() -> int:
    """Chips on this host, asked of a short-lived CHILD process.

    A chip belongs to one process at a time: a runner that initialised
    a JAX backend itself would hold the chip while the worker it spawns
    (and then waits for) tries to open it.  The child has exited — and
    released the chip — before anything is launched."""
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.local_device_count())"],
        stdout=subprocess.PIPE, text=True)
    if probe.returncode != 0:
        raise RuntimeError(
            f"local chip-count probe exited with {probe.returncode}; "
            "pass a hostfile or fix the JAX installation")
    return int(probe.stdout.split()[-1])


def main(args=None) -> int:
    args = parse_args(args)

    if (args.num_nodes >= 0 or args.num_chips >= 0) and \
            (args.include or args.exclude):
        raise ValueError("Cannot specify num_nodes/chips with include/exclude")

    resource_pool = None
    if args.tpu_pod:
        resource_pool = discover_tpu_pod()
        if resource_pool is None:
            logger.warning("--tpu_pod: no pod metadata found, falling back "
                           "to hostfile/local")
    if resource_pool is None:
        resource_pool = fetch_hostfile(args.hostfile)
    if resource_pool is None and \
            args.launcher.lower() == GCLOUD_LAUNCHER:
        # Managed pod dispatch needs no hostfile — the pod's workers ARE
        # the topology. --num_nodes supplies the worker count (hostnames
        # are placeholders; workers rank themselves via TPU_WORKER_ID).
        if args.num_nodes <= 0:
            raise ValueError(
                "--launcher gcloud without a hostfile requires "
                "--num_nodes=<pod worker count>")
        # Slot count 0 = empty slot list = full chip visibility on each
        # worker (launch.py only masks TPU_VISIBLE_CHIPS for real slots).
        resource_pool = collections.OrderedDict(
            (f"worker-{i}", args.num_chips if args.num_chips > 0 else 0)
            for i in range(args.num_nodes))
    multi_node_exec = resource_pool is not None and len(resource_pool) > 0
    if not resource_pool:
        # no hostfile: all chips of this host
        resource_pool = collections.OrderedDict(
            localhost=local_chip_count())
        args.coordinator_addr = args.coordinator_addr or "127.0.0.1"
        multi_node_exec = False

    if not multi_node_exec and args.num_nodes > 1:
        raise ValueError("num_nodes > 1 but no extra nodes via hostfile")

    active_resources = parse_inclusion_exclusion(resource_pool, args.include,
                                                 args.exclude)
    if args.num_nodes > 0:
        active_resources = collections.OrderedDict(
            list(active_resources.items())[:args.num_nodes])
    if args.num_chips > 0:
        active_resources = collections.OrderedDict(
            (h, list(range(args.num_chips))) for h in active_resources)

    env = os.environ.copy()
    coordinator = _resolve_coordinator(active_resources, args)
    world_info_base64 = encode_world_info(active_resources)
    multi_node_exec = args.force_multi or len(active_resources) > 1 or \
        args.launcher.lower() == GCLOUD_LAUNCHER   # always dispatch to pods

    if not multi_node_exec:
        cmd = [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
               f"--world_info={world_info_base64}",
               f"--coordinator_addr={coordinator}",
               f"--coordinator_port={args.coordinator_port}",
               f"--procs_per_node={args.procs_per_node}",
               "--node_rank=0",
               args.user_script] + args.user_args
    else:
        from .multinode_runner import (GcloudTPURunner, PDSHRunner,
                                       SSHRunner)
        if args.launcher.lower() == PDSH_LAUNCHER:
            runner = PDSHRunner(args, world_info_base64)
        elif args.launcher.lower() == SSH_LAUNCHER:
            runner = SSHRunner(args, world_info_base64)
        elif args.launcher.lower() == GCLOUD_LAUNCHER:
            runner = GcloudTPURunner(args, world_info_base64)
        else:
            raise NotImplementedError(f"Unknown launcher {args.launcher}")
        if not runner.backend_exists():
            raise RuntimeError(f"launcher '{args.launcher}' not installed")
        curr_path = os.path.abspath(".")
        env["PYTHONPATH"] = curr_path + (
            ":" + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
        for key, val in _collect_exports(env).items():
            runner.add_export(key, val)
        cmd = runner.get_cmd(env, active_resources, coordinator)

    logger.info(f"cmd = {' '.join(cmd)}")
    result = subprocess.Popen(cmd, env=env)
    result.wait()
    return result.returncode


if __name__ == "__main__":
    sys.exit(main())
