"""Frozen copies of the two fabrics' minimal deterministic routing.

The Megafly of the paper's section 4 (minimal routing, D-mod-k spine
choice inside a group, the forced spines of a group pair outside it) and
the three-level k-ary fat-tree (D-mod-k on the way up and down), each
built from the numbers in a benchmark configuration file.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Megafly:
    n_groups: int = 65
    leaves_per_group: int = 8
    spines_per_group: int = 8
    nodes_per_leaf: int = 8

    # ---- derived sizes ---------------------------------------------------
    @property
    def nodes_per_group(self) -> int:
        return self.leaves_per_group * self.nodes_per_leaf

    @property
    def n_nodes(self) -> int:
        return self.n_groups * self.nodes_per_group

    @property
    def switches_per_group(self) -> int:
        return self.leaves_per_group + self.spines_per_group

    @property
    def n_switches(self) -> int:
        return self.n_groups * self.switches_per_group

    @property
    def radix(self) -> int:
        return self.nodes_per_leaf + self.spines_per_group

    @property
    def n_node_links(self) -> int:
        return self.n_nodes

    @property
    def n_ls_links(self) -> int:  # leaf-spine
        return self.n_groups * self.leaves_per_group * self.spines_per_group

    @property
    def n_global_links(self) -> int:
        return self.n_groups * (self.n_groups - 1) // 2

    @property
    def n_links(self) -> int:
        return self.n_node_links + self.n_ls_links + self.n_global_links

    @property
    def n_ports(self) -> int:  # port-ends, the paper's "links" count
        return 2 * self.n_links

    @property
    def max_hops(self) -> int:
        return 5

    # ---- link ids ---------------------------------------------------------
    def node_link(self, n):
        return np.asarray(n)

    def ls_link(self, g, leaf, spine):
        return (self.n_node_links
                + (np.asarray(g) * self.leaves_per_group + np.asarray(leaf))
                * self.spines_per_group + np.asarray(spine))

    def global_link(self, g, h):
        g, h = np.asarray(g), np.asarray(h)
        lo, hi = np.minimum(g, h), np.maximum(g, h)
        G = self.n_groups
        # index into the upper-triangular pair list
        idx = lo * G - lo * (lo + 1) // 2 + (hi - lo - 1)
        return self.n_node_links + self.n_ls_links + idx

    def peer_port(self, g, h):
        """Global-port index (0..63) used by group g to reach group h."""
        g, h = np.asarray(g), np.asarray(h)
        return np.where(h < g, h, h - 1)

    def global_spine(self, g, h):
        """Spine in group g owning the global link to group h."""
        return self.peer_port(g, h) // self.spines_per_group

    # ---- node coordinates --------------------------------------------------
    def node_group(self, n):
        return np.asarray(n) // self.nodes_per_group

    def node_leaf(self, n):
        return (np.asarray(n) % self.nodes_per_group) // self.nodes_per_leaf

    # ---- routing ------------------------------------------------------------
    def routes(self, src, dst):
        """Vectorized minimal deterministic routing.

        src, dst: int arrays (M,).  Returns (links (M, max_hops) int32 with -1
        padding, n_hops (M,) int32).  Directions are implicit: direction bit =
        position parity is NOT valid here, so we also return dirs (M, max_hops)
        in {0,1}: 0 = lower-id endpoint transmits, 1 = higher-id endpoint.
        For power accounting only the link id matters; for serialization we
        track per-direction occupancy = 2*link + dir.
        """
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        M = src.shape[0]
        H = self.max_hops
        links = np.full((M, H), -1, np.int64)
        dirs = np.zeros((M, H), np.int64)

        gs, gd = self.node_group(src), self.node_group(dst)
        ls, ld = self.node_leaf(src), self.node_leaf(dst)
        same = src == dst
        same_leaf = (~same) & (gs == gd) & (ls == ld)
        intra = (~same) & (gs == gd) & (ls != ld)
        inter = gs != gd

        nl_s = self.node_link(src)      # node -> leaf (up: dir 0)
        nl_d = self.node_link(dst)      # leaf -> node (down: dir 1)

        # same leaf: [src->leaf, leaf->dst]
        links[same_leaf, 0] = nl_s[same_leaf]
        links[same_leaf, 1] = nl_d[same_leaf]
        dirs[same_leaf, 0] = 0
        dirs[same_leaf, 1] = 1

        # intra group: spine by D-mod-k on destination node id
        sp = dst % self.spines_per_group
        up = self.ls_link(gs, ls, sp)
        dn = self.ls_link(gd, ld, sp)
        for (m, arr, d) in ((0, nl_s, 0), (1, up, 0), (2, dn, 1), (3, nl_d, 1)):
            links[intra, m] = arr[intra]
            dirs[intra, m] = d

        # inter group: forced spine on both sides of the global link
        sp_s = self.global_spine(gs, gd)
        sp_d = self.global_spine(gd, gs)
        up_i = self.ls_link(gs, ls, sp_s)
        gl = self.global_link(gs, gd)
        gdir = np.where(gs < gd, 0, 1)
        dn_i = self.ls_link(gd, ld, sp_d)
        for (m, arr, d) in ((0, nl_s, 0), (1, up_i, 0), (2, gl, None),
                            (3, dn_i, 1), (4, nl_d, 1)):
            links[inter, m] = arr[inter]
            dirs[inter, m] = gdir[inter] if d is None else d

        n_hops = np.where(same, 0,
                          np.where(same_leaf, 2, np.where(intra, 4, 5)))
        return links.astype(np.int32), dirs.astype(np.int32), \
            n_hops.astype(np.int32)

    def hop_distance(self, src, dst):
        return self.routes(np.atleast_1d(src), np.atleast_1d(dst))[2]


@dataclasses.dataclass(frozen=True)
class FatTree:
    k: int = 8

    def __post_init__(self):
        assert self.k % 2 == 0, "fat-tree arity must be even"

    # ---- derived sizes ---------------------------------------------------
    @property
    def half(self) -> int:
        return self.k // 2

    @property
    def n_pods(self) -> int:
        return self.k

    @property
    def nodes_per_edge(self) -> int:
        return self.half

    @property
    def nodes_per_pod(self) -> int:
        return self.half * self.half

    @property
    def n_nodes(self) -> int:
        return self.k * self.nodes_per_pod

    @property
    def n_core(self) -> int:
        return self.half * self.half

    @property
    def n_switches(self) -> int:
        return self.k * self.k + self.n_core      # edge+agg per pod + core

    @property
    def n_node_links(self) -> int:
        return self.n_nodes

    @property
    def n_ea_links(self) -> int:
        return self.k * self.half * self.half

    @property
    def n_ac_links(self) -> int:
        return self.k * self.half * self.half

    @property
    def n_links(self) -> int:
        return self.n_node_links + self.n_ea_links + self.n_ac_links

    @property
    def n_ports(self) -> int:
        return 2 * self.n_links

    @property
    def max_hops(self) -> int:
        return 6

    # ---- link ids ----------------------------------------------------------
    def node_link(self, n):
        return np.asarray(n)

    def ea_link(self, pod, edge, agg):
        h = self.half
        return (self.n_node_links
                + (np.asarray(pod) * h + np.asarray(edge)) * h
                + np.asarray(agg))

    def ac_link(self, pod, agg, core):
        """core is a GLOBAL core id in agg's range [agg*h, (agg+1)*h)."""
        h = self.half
        slot = np.asarray(core) - np.asarray(agg) * h
        return (self.n_node_links + self.n_ea_links
                + (np.asarray(pod) * h + np.asarray(agg)) * h + slot)

    # ---- coordinates ---------------------------------------------------------
    def node_pod(self, n):
        return np.asarray(n) // self.nodes_per_pod

    def node_edge(self, n):
        return (np.asarray(n) % self.nodes_per_pod) // self.nodes_per_edge

    # ---- routing ---------------------------------------------------------------
    def routes(self, src, dst):
        """Deterministic minimal D-mod-k.  Same contract as Megafly.routes:
        (links (M, max_hops) int32 -1-padded, dirs, n_hops)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        M = src.shape[0]
        h = self.half
        links = np.full((M, self.max_hops), -1, np.int64)
        dirs = np.zeros((M, self.max_hops), np.int64)

        ps, pd = self.node_pod(src), self.node_pod(dst)
        es, ed = self.node_edge(src), self.node_edge(dst)
        same = src == dst
        same_edge = (~same) & (ps == pd) & (es == ed)
        intra = (~same) & (ps == pd) & (es != ed)
        inter = ps != pd

        nl_s, nl_d = self.node_link(src), self.node_link(dst)

        links[same_edge, 0] = nl_s[same_edge]
        links[same_edge, 1] = nl_d[same_edge]
        dirs[same_edge, 1] = 1

        # intra pod via aggregation dst % h (D-mod-k on the up choice)
        agg = dst % h
        up = self.ea_link(ps, es, agg)
        dn = self.ea_link(pd, ed, agg)
        for m, arr, d in ((0, nl_s, 0), (1, up, 0), (2, dn, 1), (3, nl_d, 1)):
            links[intra, m] = arr[intra]
            dirs[intra, m] = d

        # inter pod: agg = dst % h; core slot = (dst // h) % h within agg's
        # range — the D-mod-k pair makes the down-path unique per dst
        agg_i = dst % h
        core = agg_i * h + (dst // h) % h
        up1 = self.ea_link(ps, es, agg_i)
        up2 = self.ac_link(ps, agg_i, core)
        dn2 = self.ac_link(pd, agg_i, core)
        dn1 = self.ea_link(pd, ed, agg_i)
        for m, arr, d in ((0, nl_s, 0), (1, up1, 0), (2, up2, 0),
                          (3, dn2, 1), (4, dn1, 1), (5, nl_d, 1)):
            links[inter, m] = arr[inter]
            dirs[inter, m] = d

        n_hops = np.where(same, 0,
                          np.where(same_edge, 2, np.where(intra, 4, 6)))
        return links.astype(np.int32), dirs.astype(np.int32), \
            n_hops.astype(np.int32)

    def hop_distance(self, src, dst):
        return self.routes(np.atleast_1d(src), np.atleast_1d(dst))[2]


KINDS = {"megafly": Megafly, "fattree": FatTree}


def build(spec: dict):
    """A configuration's ``topology`` entry -> fabric."""
    return KINDS[spec["kind"]](**spec["params"])
