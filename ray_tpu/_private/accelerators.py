"""Accelerator (TPU) detection and slice topology.

Equivalent of the reference's TPUAcceleratorManager
(python/ray/_private/accelerators/tpu.py:71): detects chips per host, pod
type, and slice membership; sets chip-visibility env vars for workers; and
synthesizes slice-level resources so gang scheduling can target whole
slices (tpu.py:314,381). Detection order: explicit env override → the
chips' device nodes under /dev → none.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Optional

# v5e host topology default: 4 chips/host (v4: 4, v5p: 4; v5e can be 1/4/8)
DEFAULT_CHIPS_PER_HOST = 4


def detect_tpu_chips() -> int:
    """Number of TPU chips attached to this host: RAY_TPU_NUM_TPUS if
    set, else the chips' device nodes — /dev/accel* (v4 and older
    drivers) or the numbered VFIO groups under /dev/vfio (v5e and later),
    the census the reference takes (tpu.py
    get_current_node_num_accelerators).

    It never asks JAX: with libtpu installed a process that initialises
    JAX takes the chip, and the chip must be left for the worker leased
    to TPU work. TPU_CHIPS_PER_HOST_BOUNDS is not consulted either — it
    describes the host's topology, not what is attached (a machine given
    one chip of a v5litepod-4 still reads "2,2,1"). A malformed override
    or an unreadable /dev raises: a node that cannot count its chips
    must not quietly advertise none."""
    env = os.environ.get("RAY_TPU_NUM_TPUS")
    if env is not None:
        return int(env)
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return sum(e.isdigit() for e in os.listdir("/dev/vfio"))
    except FileNotFoundError:
        return 0


def tpu_pod_type() -> Optional[str]:
    """E.g. "v5litepod-64" (reference: tpu.py accelerator type from GCE
    metadata / GKE env)."""
    return os.environ.get("TPU_ACCELERATOR_TYPE") or \
        os.environ.get("RAY_TPU_POD_TYPE")


def tpu_slice_id() -> str:
    """Identity of the slice this host belongs to. Hosts in the same slice
    share an ICI domain; the SLICE placement strategy gangs over it."""
    return os.environ.get("TPU_WORKER_HOSTNAMES",
                          os.environ.get("RAY_TPU_SLICE_ID", ""))


def tpu_worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def num_hosts_in_slice() -> int:
    pod = tpu_pod_type()
    if not pod:
        return 1
    try:
        chips = int(pod.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 1
    return max(1, chips // DEFAULT_CHIPS_PER_HOST)


def slice_resources() -> Dict[str, float]:
    """Synthesized resources for gang scheduling: per-host chips plus the
    slice-head marker on worker 0 (reference: tpu.py:314,381
    `TPU-{pod_type}-head`)."""
    res: Dict[str, float] = {}
    chips = detect_tpu_chips()
    if chips:
        res["TPU"] = float(chips)
        pod = tpu_pod_type()
        if pod:
            res[f"TPU-{pod}"] = float(chips)
            if tpu_worker_id() == 0:
                res[f"TPU-{pod}-head"] = 1.0
    return res


def set_visible_chips_env(env: Dict[str, str], chip_ids: list) -> None:
    """Restrict a worker process to specific chips (reference: tpu.py:31
    TPU_VISIBLE_CHIPS)."""
    env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chip_ids)
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"1,{len(chip_ids)},1"
