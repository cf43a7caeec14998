"""Plain checks of a routed CGRA design against its source application.

Nothing here imports the compiler.  A design is read as data: its
netlist's nodes (kind, op, latency, depth, constants), its branches
(driver, sink, port, registers on the branch), its placement and its
routes (tile hops).  The fabric is the configuration's.

``behaviour`` evaluates a graph with the IR's documented semantics:

- a 16-bit value domain; PE ops as the IR defines them; edges in the
  predicate band (ports 80..89) give the op its last argument, edges in
  the control band (ports >= 90) carry no data;
- dense (statically scheduled) graphs: every node fires every cycle; a
  node with latency L outputs at cycle t what its op gives for its inputs
  at t - L (0 before that); a branch with n registers delays by n cycles;
  outputs are compared from their arrival latency (the longest input
  path's delay) on, sample for sample;
- sparse (ready-valid) graphs: the k-th output token is the op applied to
  the k-th input tokens, whatever the buffering; registers are FIFOs and
  carry tokens unchanged.

``mismatches`` counts the output samples in which a design differs from
its source app; ``illegal`` counts placement and routing rules the design
breaks.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

PRED_PORT, CONTROL_PORT = 80, 90
MASK = 0xFFFF


def _op(op: str, args: List[np.ndarray]) -> np.ndarray:
    a = args
    if op == "add":
        return (a[0] + a[1]) & MASK
    if op == "sub":
        return (a[0] - a[1]) & MASK
    if op == "mul":
        return (a[0] * a[1]) & MASK
    if op == "and":
        return a[0] & a[1]
    if op == "or":
        return a[0] | a[1]
    if op == "xor":
        return a[0] ^ a[1]
    if op == "shr":
        return (a[0] >> (a[1] & 0xF)) & MASK
    if op == "shl":
        return (a[0] << (a[1] & 0xF)) & MASK
    if op == "min":
        return np.minimum(a[0], a[1])
    if op == "max":
        return np.maximum(a[0], a[1])
    if op == "abs":
        return np.where(a[0] < 0x8000, a[0], (-a[0]) & MASK)
    cmp = {"gt": np.greater, "lt": np.less, "eq": np.equal,
           "ne": np.not_equal, "ge": np.greater_equal, "le": np.less_equal}
    if op in cmp:
        return cmp[op](a[0], a[1]).astype(np.int64)
    if op == "mux":
        return np.where(a[0] & 1, a[1], a[2])
    if op == "pass":
        return a[0]
    if op == "steer":
        return np.where(a[1] & 1, a[0], 0)
    if op in ("sel", "phi"):
        return np.where(a[2] & 1, a[0], a[1])
    raise NotImplementedError(f"PE op {op!r}")


def node_latency(nd) -> int:
    kind = nd.kind
    if kind == "reg":
        return 1
    if kind == "rf":
        return nd.depth
    if kind == "fifo":
        return 1
    if kind == "mem":
        return max(1, nd.depth) if nd.op == "delay" else max(1, nd.latency)
    if kind == "pe":
        return nd.latency + (1 if nd.input_reg else 0)
    return nd.latency


def _eval(nd, args: List[np.ndarray]) -> np.ndarray:
    if nd.kind == "pe":
        return _op(nd.op, args)
    if nd.kind == "mem":
        if nd.op == "rom":
            table = np.asarray(nd.meta.get("table", []) or [0], np.int64)
            return table[(args[0] if args else 0) % len(table)]
        if nd.op == "accum":
            raise NotImplementedError("MEM accumulators")
        return args[0]
    if nd.kind in ("reg", "rf", "fifo", "output"):
        return args[0]
    raise NotImplementedError(f"node kind {nd.kind!r}")


def _shift(x: np.ndarray, d: int) -> np.ndarray:
    if d <= 0:
        return x
    return np.concatenate([np.zeros(min(d, len(x)), x.dtype), x[:-d]])


def _topo(nodes, edges) -> List[str]:
    indeg = {n: 0 for n in nodes}
    succ: Dict[str, List[str]] = {n: [] for n in nodes}
    for src, dst, _, _ in edges:
        indeg[dst] += 1
        succ[src].append(dst)
    ready = sorted(n for n, k in indeg.items() if k == 0)
    order = []
    while ready:
        n = ready.pop()
        order.append(n)
        for m in succ[n]:
            indeg[m] -= 1
            if indeg[m] == 0:
                ready.append(m)
    if len(order) != len(nodes):
        raise ValueError("graph has a cycle")
    return order


def behaviour(nodes: Dict[str, object], edges: List[Tuple[str, str, int, int]],
              inputs: Dict[str, np.ndarray], cycles: int, sparse: bool):
    """Output streams and their arrival latencies of a graph.

    ``edges`` are ``(src, dst, port, delay)``; control-band edges are
    ignored.  Dense graphs run ``cycles`` cycles; sparse graphs map the
    input tokens one by one (delays and latencies carry no meaning)."""
    data = [e for e in edges if e[2] < CONTROL_PORT]
    into: Dict[str, List[Tuple[str, str, int, int]]] = {n: [] for n in nodes}
    for e in data:
        into[e[1]].append(e)
    n = cycles if not sparse else min(len(v) for v in inputs.values())
    val: Dict[str, np.ndarray] = {}
    arrival: Dict[str, int] = {}
    for name in _topo(nodes, data):
        nd = nodes[name]
        ins = sorted(into[name], key=lambda e: e[2])
        lat = 0 if sparse else node_latency(nd)
        if nd.kind == "input":
            v = np.zeros(n, np.int64)
            src = np.asarray(inputs.get(name, ()), np.int64)[:n]
            v[:len(src)] = src
            val[name], arrival[name] = v, lat
            continue
        if nd.kind == "const":
            val[name] = np.full(n, nd.value & MASK, np.int64)
            arrival[name] = 0
            continue
        args = [val[s] if sparse else _shift(val[s], d)
                for s, _, _, d in ins]
        val[name] = _shift(_eval(nd, args), lat)
        arrival[name] = lat + max(
            (arrival[s] + (0 if sparse else d) for s, _, _, d in ins),
            default=0)
    outs = {k: val[k] for k, nd in nodes.items() if nd.kind == "output"}
    lats = {k: arrival[k] for k in outs}
    return outs, lats


def dfg_graph(g):
    """(nodes, edges) of a source application graph."""
    return dict(g.nodes), [(e.src, e.dst, e.port, 0) for e in g.edges]


def design_graph(design):
    """(nodes, edges) of a routed design: netlist nodes and folded
    constants; each branch delays by its registers."""
    nl = design.netlist
    nodes = {**nl.nodes, **nl.const_nodes}
    edges = [(c, sink, port, 0) for c, sink, port in nl.consts]
    edges += [(b.driver, b.sink, b.port, b.n_regs) for b in nl.branches]
    return nodes, edges


def mismatches(source, design, inputs: Dict[str, np.ndarray], samples: int,
               sparse: bool) -> int:
    """Output samples (out of ``samples`` per source output) in which the
    design differs from the source app; a missing output counts whole."""
    src_nodes, src_edges = source
    des_nodes, des_edges = design
    if sparse:
        a, _ = behaviour(src_nodes, src_edges, inputs, 0, True)
        b, _ = behaviour(des_nodes, des_edges, inputs, 0, True)
        return sum(int(np.sum(a[k] != b[k])) if k in b else len(a[k])
                   for k in a)
    # enough cycles for the slower graph to fill and give ``samples``
    probe = samples + 1
    _, la = behaviour(src_nodes, src_edges, inputs, probe, False)
    _, lb = behaviour(des_nodes, des_edges, inputs, probe, False)
    cycles = samples + max(list(la.values()) + list(lb.values())) + 1
    a, la = behaviour(src_nodes, src_edges, inputs, cycles, False)
    b, lb = behaviour(des_nodes, des_edges, inputs, cycles, False)
    bad = 0
    for k, stream in a.items():
        if k not in b:
            bad += samples
            continue
        bad += int(np.sum(stream[la[k]:la[k] + samples]
                          != b[k][lb[k]:lb[k] + samples]))
    return bad


# ---------------------------------------------------------------------------
# legality


TILE_CLASS = {"pe": "pe", "rf": "pe", "fifo": "pe", "mem": "mem",
              "input": "io", "output": "io"}


def tile_kind(fabric: dict, t) -> str:
    r, c = t
    if not 0 <= c < fabric["cols"] or not -1 <= r < fabric["rows"]:
        return "outside"
    if r == -1:
        return "io"
    stride = fabric["mem_col_stride"]
    return "mem" if c % stride == stride - 1 else "pe"


def adjacent(fabric: dict, a, b) -> bool:
    if a[0] == -1:                   # IO tiles connect only into row 0
        return b == (0, a[1])
    if abs(a[0] - b[0]) + abs(a[1] - b[1]) != 1:
        return False
    return tile_kind(fabric, b) != "outside"


def illegal(design, fabric: dict) -> int:
    """Count of broken rules: a node on a tile of the wrong class or
    outside the fabric; a tile holding more nodes than it may; a branch
    with no route; a route that does not join its driver's tile to its
    sink's over adjacent tiles; a tile boundary carrying more routing
    trees of a width class than the fabric has tracks."""
    nl, place = design.netlist, design.placement
    bad = 0
    load: Dict[tuple, int] = {}
    for name, nd in nl.nodes.items():
        t = place.get(name)
        if t is None or tile_kind(fabric, tuple(t)) != TILE_CLASS[nd.kind]:
            bad += 1
            continue
        load[tuple(t)] = load.get(tuple(t), 0) + 1
    for t, k in load.items():
        if k > (fabric["io_capacity"] if t[0] == -1 else 1):
            bad += 1
    usage: Dict[tuple, set] = {}
    routed = {(rb.branch.driver, rb.branch.sink, rb.branch.port): rb
              for rb in design.routes.values()}
    for b in nl.branches:
        rb = routed.get((b.driver, b.sink, b.port))
        if rb is None or b.driver not in place or b.sink not in place:
            bad += 1
            continue
        at = tuple(place[b.driver])
        ok = True
        for h in rb.hops:
            if tuple(h.src) != at or not adjacent(fabric, at, tuple(h.dst)):
                ok = False
                break
            at = tuple(h.dst)
            wc = 16 if b.width >= 16 else 1
            usage.setdefault((tuple(h.src), at, wc), set()).add(b.driver)
        if not ok or at != tuple(place[b.sink]):
            bad += 1
    for (_, _, wc), drivers in usage.items():
        if len(drivers) > fabric["tracks16" if wc == 16 else "tracks1"]:
            bad += 1
    return bad


# ---------------------------------------------------------------------------
# timing


SEQ_OUT = ("input", "mem", "rf", "fifo")
SEQ_IN = ("output", "mem", "rf", "fifo")


def critical_path_ns(design, fabric: dict, tech: dict) -> float:
    """Longest register-to-register path of a routed design, in ns.

    A segment starts at a sequential output (inputs, memories, register
    files, FIFOs, input-registered PEs: clock-to-q plus the core's delay)
    or at a register on a route hop (clock-to-q), crosses cores of
    combinational PEs and route hops, and ends at a register on a hop or
    at a sequential input (outputs, memories, register files, FIFOs,
    input-registered PEs), through the input connection box; each segment
    adds clock-to-q, setup and skew.  Delays are the configuration's
    technology table: one core delay per tile kind, one switch-box delay
    per (kind of the tile entered, horizontal or vertical)."""
    nl = design.netlist
    overhead = tech["reg_clk_q"] + tech["reg_setup"] + tech["clk_skew"]
    core_key = {"pe": "core_pe", "mem": "core_mem", "rf": "core_rf",
                "fifo": "core_fifo", "input": "core_io", "output": "core_io"}

    def hop_ns(src, dst) -> float:
        if dst[0] < 0:
            return tech["sb_pe_v"]
        kind = "mem" if tile_kind(fabric, dst) == "mem" else "pe"
        return tech[f"sb_{kind}_{'h' if src[0] == dst[0] else 'v'}"]

    into: Dict[str, list] = {n: [] for n in nl.nodes}
    for rb in design.routes.values():
        into[rb.branch.sink].append(rb)
    worst = [-1.0]

    def walk(rb, a: float) -> float:
        for i, h in enumerate(rb.hops):
            a += hop_ns(tuple(h.src), tuple(h.dst))
            if i in rb.reg_hops:
                worst[0] = max(worst[0], a + overhead)
                a = tech["reg_clk_q"]
        return a + tech["cb_in"]

    edges = [(rb.branch.driver, rb.branch.sink, 0, 0)
             for rb in design.routes.values()]
    arrival: Dict[str, float] = {}
    for name in _topo(nl.nodes, edges):
        nd = nl.nodes[name]
        core = tech[core_key[nd.kind]]
        reg_in = nd.kind == "pe" and nd.input_reg
        if nd.kind in SEQ_OUT or reg_in:
            arrival[name] = tech["reg_clk_q"] + core
        else:
            arrival[name] = max((walk(rb, arrival[rb.branch.driver])
                                 for rb in into[name]), default=0.0) + core
        if nd.kind in SEQ_IN or reg_in:
            for rb in into[name]:
                end = walk(rb, arrival[rb.branch.driver]) + overhead
                worst[0] = max(worst[0], end)
    if worst[0] < 0:
        return overhead + tech["core_pe"]
    return worst[0]
