"""The least work of one SOAR all-reduce, from the placement alone.

A reduction tree (``parent``, ``load``) has one chip under each device
leaf (``device_leaf[d]``, load 1). Each switch lives on a chip, its
"home": a device leaf's is its chip, an internal switch's that of its
first child (by index) that has one. With the blue switches ``blue``,
Algorithm 1 of the SOAR paper (``reference.messages``) gives the messages
on every switch's up-link; each message is one whole gradient of
``nbytes`` bytes. The work the placement asks of each chip is:

* ICI: every message whose switch and parent live on different chips is
  sent by the one and received by the other; the sum, once made at the
  root's home, is received by every other chip (a broadcast: the root's
  home sends it at least once);
* HBM: a blue switch that receives w > 1 messages (its load plus its
  children's) reads them and writes one, and the destination does the
  same with the root's outgoing messages.

``least_seconds`` is the larger, over chips, of the busier ICI direction
at the chip's interconnect peak and the HBM bytes at its memory peak:
no executor of this placement can take less. The counts come from the
tree, the placement and the gradient's size, never from how an executor
buffers, orders or fuses the work.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def homes(parent: np.ndarray, device_leaf: np.ndarray) -> np.ndarray:
    """The chip each switch lives on (-1 where its subtree holds none)."""
    parent = np.asarray(parent)
    n = len(parent)
    home = np.full(n, -1, np.int64)
    for d, leaf in enumerate(device_leaf):
        home[leaf] = d
    kids = [[] for _ in range(n)]
    for v in range(n):
        if parent[v] >= 0:
            kids[parent[v]].append(v)
    for v in range(n - 1, -1, -1):          # parents precede children
        if home[v] < 0:
            home[v] = next((home[c] for c in kids[v] if home[c] >= 0), -1)
    return home


def sync_work(parent, load, device_leaf, blue, nbytes: int) -> dict:
    """Per chip: bytes sent and received over ICI and bytes of HBM read
    and written by the sums, for one all-reduce of ``nbytes`` a chip."""
    parent = np.asarray(parent, np.int64)
    load = np.asarray(load, np.int64)
    blue = np.asarray(blue, bool)
    if any(load[leaf] != 1 for leaf in device_leaf) or \
            int(load.sum()) != len(device_leaf):
        raise ValueError("expected one chip, of load 1, under each device "
                         "leaf and no other load")
    n_dev = len(device_leaf)
    home = homes(parent, device_leaf)
    msgs = reference.messages(parent, load, blue)
    recv_msgs = np.zeros(len(parent), np.int64)
    sent = np.zeros(n_dev, np.int64)
    recv = np.zeros(n_dev, np.int64)
    hbm = np.zeros(n_dev, np.int64)
    root = int(np.nonzero(parent < 0)[0][0])
    for v in range(len(parent)):
        p = parent[v]
        if p < 0:
            continue
        recv_msgs[p] += msgs[v]
        if msgs[v] and home[v] != home[p]:
            sent[home[v]] += msgs[v]
            recv[home[p]] += msgs[v]
    for v in range(len(parent)):
        w = int(load[v] + recv_msgs[v])
        if blue[v] and w > 1:
            hbm[home[v]] += w + 1
    if msgs[root] > 1:
        hbm[home[root]] += msgs[root] + 1
    others = np.arange(n_dev) != home[root]
    recv[others] += 1
    sent[home[root]] += 1
    return {"sent": sent * nbytes, "recv": recv * nbytes,
            "hbm": hbm * nbytes}


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time of ``work`` at the chip's ICI and HBM peaks."""
    ici = np.maximum(work["sent"], work["recv"]) / (
        peaks["ici_bits_per_s"] / 8.0)
    mem = work["hbm"] / peaks["hbm_bytes_per_s"]
    return float(np.maximum(ici, mem).max())
