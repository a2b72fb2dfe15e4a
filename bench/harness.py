"""One benchmark run of one cell, driven through the served path's entry.

The system under test is built once per process: the program's model
configuration (checked against the configuration file's published sizes),
the benchmark's seeded weights, a ``PartitionedServer`` and its
``RequestScheduler``.  The window then calls ``RequestScheduler.step()`` in
a loop, submitting before each step the requests that are due.  The
harness never reaches around that entry during the window; it only wraps
``PartitionedServer.step`` and ``TierExecutor.prefill_rows`` from outside
to time them and to keep the device handle of each admission's first
token, which it reads once the window has closed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from bench import flops as F
from bench import trace as T
from bench import traffic as traffic_mod
from bench import weights

BENCH = Path(__file__).resolve().parent

#: Seconds at the end of a traced run's window that the profiler records.
TRACE_SECONDS = 10.0
#: How long after the window an open-loop run waits for the first token of
#: each request sent in it before counting the request as failed.
DRAIN_SECONDS = 60.0

#: Host spans the harness writes into the profiler's trace.
SPANS = frozenset({"submit", "wait", "scheduler.step",
                   "PartitionedServer.step", "prefill_rows"})


# ------------------------------------------------------------ discovery
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_limits(name: str, directory: Path = BENCH / "limits") -> dict:
    """The limits that decide ``correct`` in a cell; none if no file."""
    path = directory / f"{name}.json"
    return load_json(path) if path.exists() else {}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str,
                  directory: Path = BENCH / "metrics") -> Callable:
    """``read(run)`` of the per-layer metric ``name``: a number, or None
    where the run holds nothing to read."""
    return load_module(directory / f"{name}.py").read


def reference_module(family: str, directory: Path = BENCH / "references"):
    return load_module(directory / f"{family}.py")


# ------------------------------------------------------------ spans
class Spans:
    """Host spans: summed seconds by name, and a profiler annotation of the
    same name around each."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.seconds: dict[str, float] = collections.defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = self.clock()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] += self.clock() - t


# ------------------------------------------------------------ system
@dataclasses.dataclass
class System:
    spec: dict
    traffic: dict
    cfg: Any
    params: Any
    server: Any
    mesh: Any
    devices: list
    spans: Spans
    shapes: Any
    shardings: Any
    #: (scheduler step count, admitted rows, device first tokens) per
    #: admission prefill, in call order.
    prefills: list = dataclasses.field(default_factory=list)

    @property
    def executor(self):
        return self.server.executor

    @property
    def sched(self):
        return self.server.scheduler

    @property
    def branches(self) -> tuple[int, ...]:
        """Exit heads the installed plan evaluates, in layer order."""
        return tuple(sorted(b for s in self.executor.segments
                            for b in s.branches))

    def depth_after_exit(self, layer: int) -> int:
        """Layers that hold K/V for a token that exited after ``layer``:
        those up to the end of the tier that holds the exit head."""
        for s in self.executor.segments:
            if s.layer_lo < layer <= s.layer_hi:
                return s.layer_hi
        raise ValueError(f"no tier holds layer {layer}")


PUBLISHED_KEYS = {  # configuration file key -> program ModelConfig field
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}


def head_dim(spec: dict) -> int:
    return spec.get("head_dim") or (
        spec["hidden_size"] // spec["num_attention_heads"])


def program_config(spec: dict, traffic: dict):
    """The program's ModelConfig for this configuration and mix, with its
    sizes checked against the file's: a program preset that drifted from
    the published widths is an error, not a different benchmark."""
    from repro.configs import get_config

    over = dict(spec.get("program", {}))
    for key in ("branch_layers",):
        if key in over:
            over[key] = tuple(over[key])
    threshold = traffic.get("exit", {}).get("threshold")
    if threshold is not None:
        over["exit_threshold"] = float(threshold)
    cfg = dataclasses.replace(get_config(spec["model"]), **over)
    want = {field: spec[key] for key, field in PUBLISHED_KEYS.items()}
    want.update(head_dim=head_dim(spec), use_qk_norm=bool(spec.get("qk_norm")),
                branch_layers=tuple(spec["branch_layers"]),
                tie_embeddings=bool(spec["tie_word_embeddings"]))
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file: {got} != {want}")
    return cfg


def build_system(spec: dict, traffic: dict, seed: int) -> System:
    from jax.sharding import SingleDeviceSharding

    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.serving import PartitionedServer
    from repro.sharding.policy import make_policy

    cfg = program_config(spec, traffic)
    if len(spec["cuts"]) != 1:
        raise NotImplementedError("the harness serves K=2 (one cut)")
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    mesh = None
    if spec.get("mesh"):
        data, model = spec["mesh"]
        mesh = make_local_mesh(data=data, model=model)
        shardings = make_policy(mesh, cfg).params_shardings(shapes)
        devices = list(mesh.devices.flat)
    else:
        devices = [jax.devices()[0]]
        shardings = SingleDeviceSharding(devices[0])
    params = weights.make_params(shapes, seed, shardings)
    server = PartitionedServer(
        cfg, params, spec["cuts"][0], slots=spec["slots"],
        context_len=spec["context"], mesh=mesh)
    system = System(spec, traffic, cfg, params, server, mesh, devices,
                    Spans(), shapes, shardings)
    _wrap(system)
    return system


def reseed(system: System, seed: int) -> None:
    """New weights from ``seed`` and a fresh scheduler on the same compiled
    programs (the control script reads many seeds in one process)."""
    ex = system.executor
    system.params = ex.params = system.server.params = None
    system.server._scheduler = None
    system.prefills.clear()
    gc.collect()
    system.params = weights.make_params(system.shapes, seed,
                                        system.shardings)
    ex.params = system.server.params = (
        ex.policy.shard_params(system.params) if ex.sharded
        else system.params)
    ex.install(ex.segments)


def _wrap(system: System) -> None:
    server, ex, spans = system.server, system.executor, system.spans
    step, prefill_rows = server.step, ex.prefill_rows

    def timed_step(*args, **kw):
        with spans("PartitionedServer.step"):
            return step(*args, **kw)

    def timed_prefill(caches, tokens, rows):
        with spans("prefill_rows"):
            caches, tok0 = prefill_rows(caches, tokens, rows)
        system.prefills.append(
            (system.sched.step_count, np.array(rows), tok0))
        return caches, tok0

    server.step = timed_step
    ex.prefill_rows = timed_prefill


# ------------------------------------------------------------ set-up
def warm_up(system: System) -> int:
    """Run every shape the cell's traffic can produce once, through the
    scheduler: each prompt length at each admission group width of the
    bucket ladder (every ``(prompt_len, n)`` prefill program), every group
    width's host-side scatter of first tokens, and each downstream bucket
    of the decode step.  Returns the number of warm-up steps."""
    from repro.core.multitier import bucket_for, bucket_ladder

    sched, ex, slots = system.sched, system.executor, system.spec["slots"]
    lengths = traffic_mod.prompt_lengths(system.traffic)
    vocab = system.cfg.vocab_size
    ladder = bucket_ladder(slots)
    steps = 0

    def admit(group: int, plen: int, bucket: int):
        nonlocal steps
        for _ in range(group):
            sched.submit(np.arange(plen, dtype=np.int32) % vocab, 1)
        ex._hints = {i: bucket for i in range(1, len(ex.segments))}
        while sched.active.any() or sched.queue:
            sched.step()
            steps += 1

    for group in range(1, slots + 1):  # every group width's scatter
        admit(group, lengths[0], bucket_for(group, slots))
    for plen in lengths[1:]:
        for n in ladder:
            admit(n, plen, n)
    for b in ladder:  # each downstream bucket with one live row
        admit(1, lengths[0], b)
    ex.install(ex.segments)  # fresh bucket hints for the window
    system.prefills.clear()
    return steps


def calibrate_exit(system: System, seed: int) -> dict:
    """The exit regime's set-up: scale the exit head's norm gain so that
    the median normalized entropy the served path reports at that head,
    over a seeded calibration decode, sits at the mix's threshold.

    The calibration decode serves ``requests`` requests of the mix's own
    sizes (its ``requests`` stratified quantiles of prompt and output
    length), so the positions and contexts it reads are those the window
    serves.  The threshold is a constant of the compiled programs, so
    moving it per seed would compile them anew in every run; the gain is a
    weight.  For near-uniform heads the entropy deficit ``1 - H / log V``
    grows with the square of the logits' scale, so the gain is
    ``sqrt((1 - threshold) / (1 - median))``, applied ``passes`` times.
    Returns what it read."""
    ex_spec = system.traffic.get("exit", {})
    cal = ex_spec.get("calibrate")
    if not cal:
        return {}
    layer = int(ex_spec["layer"])
    thr = float(system.cfg.exit_threshold)
    idx = system.cfg.branch_layers.index(layer)
    sched, vocab = system.sched, system.cfg.vocab_size
    n = int(cal["requests"])
    mix = system.traffic
    plen = traffic_mod.lengths(mix["prompt"], n,
                               mix["prompt"].get("multiple", 1))
    budget = traffic_mod.lengths(mix["output"], n)
    rng = np.random.default_rng([int(seed), 2])
    out = {"medians": [], "gains": [], "tokens": []}
    for _ in range(int(cal["passes"])):
        for p, b in zip(plen, budget):
            sched.submit(rng.integers(0, vocab, int(p), dtype=np.int32),
                         int(b))
        ents = []
        for rep in sched.run():
            res = rep.server_report.tier_result
            ents.append(np.asarray(res.branch_entropy[layer])[res.active])
        ents = np.concatenate(ents)
        med = float(np.median(ents))
        gain = float(np.sqrt((1.0 - thr) / (1.0 - med)))
        _scale_branch_gain(system, idx, gain)
        out["medians"].append(med)
        out["gains"].append(gain)
        out["tokens"].append(len(ents))
    system.executor.install(system.executor.segments)
    system.prefills.clear()
    return out


def _scale_branch_gain(system: System, idx: int, gain: float) -> None:
    ex = system.executor
    for params in {id(p): p for p in (system.params, ex.params)}.values():
        scale = params["branches"]["scale"]
        params["branches"]["scale"] = scale.at[idx].multiply(
            np.asarray(gain, scale.dtype))


# ------------------------------------------------------------ window
@dataclasses.dataclass
class Req:
    rid: int
    plen: int
    budget: int
    send: float  # scheduled send time (host clock)
    prompt: np.ndarray
    sent: float = 0.0  # when it was submitted (host clock)
    admit_step: float | None = None  # start of the step() that admitted it
    emits: list = dataclasses.field(default_factory=list)  # host times


@dataclasses.dataclass
class Step:
    start: float
    end: float
    live: int
    hops: tuple  # ((survivors, bucket), ...) of the final run
    syncs: int
    retries: int
    admitted: tuple  # rids admitted (prefilled) at its start
    server_s: float  # host seconds inside ``PartitionedServer.step``


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    spec: dict
    traffic: dict
    dims: F.Dims
    peaks: dict
    chips: int
    branches: tuple
    depth_cut: int
    window: tuple[float, float]
    steps: list
    reqs: dict
    setup_s: float = 0.0
    #: per token: (step index, position, exited, deep context, request id)
    tokens: list = dataclasses.field(default_factory=list)
    trace: dict | None = None


def serve(system: System, requests, seconds: float, *,
          clock=time.perf_counter, trace_dir: str | None = None,
          on_open: Callable[[], Any] | None = None):
    """The measured window.  The mix's ``arrivals`` is "poisson" (send each
    request at its scheduled time) or "backlog" (keep ``slots`` requests
    queued, the slots full when the window opens).  ``on_open`` is called
    just before the window's clock starts.  Returns (window, steps, reqs,
    traced step range or None)."""
    sched, spans = system.sched, system.spans
    kind = system.traffic["arrivals"]
    slots = system.spec["slots"]
    stop_on_exit = bool(system.traffic.get("stop_on_exit", False))
    reqs: dict[int, Req] = {}
    steps: list[Step] = []
    pending = collections.deque(requests)
    ex = system.executor

    def send(r, when):
        with spans("submit"):
            rid = sched.submit(r.prompt, r.budget, stop_on_exit=stop_on_exit)
        reqs[rid] = Req(rid, len(r.prompt), r.budget, when, r.prompt,
                        sent=clock())

    def step():
        syncs, retries = ex.host_syncs, ex.overflow_retries
        inside = spans.seconds["PartitionedServer.step"]
        t = clock()
        with spans("scheduler.step"):
            rep = sched.step()
        end = clock()
        if rep is None:
            return
        for rid in rep.admitted:
            reqs[rid].admit_step = t
        for rid in rep.emitted:
            reqs[rid].emits.append(end)
        res = rep.server_report.tier_result
        steps.append(Step(
            t, end, rep.live,
            tuple((h.survivors, h.bucket) for h in res.compaction),
            ex.host_syncs - syncs, ex.overflow_retries - retries,
            tuple(rep.admitted),
            spans.seconds["PartitionedServer.step"] - inside))

    if kind == "backlog":
        while len(sched.queue) < 2 * slots and pending:
            send(pending.popleft(), clock())
        step()  # the slots are full when the window opens
        steps.clear()
    if on_open is not None:
        on_open()
    traced = None
    t0 = clock()
    if kind == "backlog":
        for r in reqs.values():
            r.send = min(r.send, t0)
    t_trace = t0 + max(0.0, seconds - TRACE_SECONDS)
    while True:
        now = clock()
        if now - t0 >= seconds:
            break
        if trace_dir and traced is None and now >= t_trace:
            jax.profiler.start_trace(trace_dir)
            traced = [len(steps), None]
        if kind == "backlog":
            while len(sched.queue) < slots and pending:
                send(pending.popleft(), clock())
        else:
            while pending and t0 + pending[0].arrival_s <= now:
                r = pending.popleft()
                send(r, t0 + r.arrival_s)
        if sched.queue or sched.active.any():
            step()
        else:
            nxt = t0 + (pending[0].arrival_s if pending else seconds)
            with spans("wait"):
                time.sleep(max(0.0, min(nxt, t0 + seconds) - clock()))
    t1 = t0 + seconds
    if traced is not None:
        traced[1] = len(steps)
        jax.profiler.stop_trace()
    if kind != "backlog":
        deadline = clock() + DRAIN_SECONDS
        while (any(not r.emits for r in reqs.values())
               and (sched.queue or sched.active.any())
               and clock() < deadline):
            step()
    return (t0, t1), steps, reqs, traced


def token_records(system: System, steps, reqs) -> list:
    """(step index, position, exited, deep context, rid) of every token the
    recorded steps emitted, from the scheduler's results.  ``deep
    context`` is how many positions the later tiers hold K/V for when the
    token is decoded: its own and every earlier one that did not exit."""
    end_to_step = {s.end: i for i, s in enumerate(steps)}
    out = []
    for rid, r in reqs.items():
        res = system.sched.results.get(rid)
        if res is None:
            continue
        holes = 0
        for j, t in enumerate(r.emits):
            pos = r.plen + j
            ex = bool(res.exited[j])
            if t in end_to_step:
                out.append((end_to_step[t], pos, ex, pos + 1 - holes, rid))
            holes += ex
    return out


# ------------------------------------------------------------ trace
def reduce_trace(trace_dir: str, chips: int, traced) -> dict:
    """Device time over the traced slice of the window: per chip busy time
    and op events, and the host spans on the trace's clock.  The slice
    runs from the first annotated host span to the end of the last
    ``scheduler.step`` recorded before the profiler stopped."""
    tr = T.load(T.find_xplanes(trace_dir), SPANS)
    step_spans = sorted((e for e in tr.host if e.name == "scheduler.step"),
                        key=lambda e: e.start)
    i0, i1 = traced
    if not step_spans or i1 <= i0 or not tr.ops:
        return {}
    lo = min(e.start for e in tr.host)
    hi = step_spans[min(i1 - i0, len(step_spans)) - 1].end
    used = sorted(tr.ops)[:chips]
    busy = {c: T.union_ns(tr.ops[c], lo, hi) for c in used}
    gaps = T.idle_gaps(tr.ops[used[0]], lo, hi) if used else []
    spans = [e for e in tr.host if e.start < hi and e.end > lo]
    return {
        "lo": lo, "hi": hi, "steps": (i0, i1), "chips": used,
        "ops": {c: tr.ops[c] for c in used},
        "modules": {c: tr.modules.get(c, []) for c in used},
        "busy_ns": busy,
        "window_ns": hi - lo,
        "breakdown": {
            "device_ops": T.top_ops(tr.ops[used[0]], lo, hi) if used
            else [],
            "idle_gaps": T.attribute_gaps(gaps, spans),
        },
    }


# ------------------------------------------------------------ outputs
def sample_finished(system: System, reqs: dict, count: int,
                    seed: int) -> list[int]:
    """``count`` finished requests, drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    res = system.sched.results
    done = [rid for rid in sorted(reqs)
            if rid in res and res[rid].done and res[rid].status == "ok"]
    if not done:
        return []
    length = {rid: reqs[rid].plen + len(res[rid].tokens) for rid in done}
    longest = max(done, key=lambda rid: (length[rid], -rid))
    rest = [rid for rid in done if rid != longest]
    rng = np.random.default_rng([int(seed), 1])
    pick = rng.choice(len(rest), size=min(count - 1, len(rest)),
                      replace=False) if rest and count > 1 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def first_tokens(system: System, rids) -> dict[int, int]:
    """The token each request's admission prefill fed to its first decode
    step, read from the device handles kept by the prefill wrapper."""
    res = system.sched.results
    want = {(res[r].admitted_step, res[r].slot): r for r in rids}
    out = {}
    for step_count, rows, tok0 in system.prefills:
        hit = [(i, want[(step_count, int(row))]) for i, row in enumerate(rows)
               if (step_count, int(row)) in want]
        if hit:
            host = np.asarray(tok0)
            for i, r in hit:
                out[r] = int(host[i])
    return out


def served_sequences(system: System, reqs: dict, rids, tok0: dict) -> list:
    """Per sampled request: the token inputs, the K/V depth of each
    position, the head that produced each output and the served target,
    padded to one length (the context rounded up to 128)."""
    L = system.cfg.num_layers
    pad = -(-system.spec["context"] // 128) * 128
    out = []
    for rid in rids:
        r, res = reqs[rid], system.sched.results[rid]
        toks = [int(t) for t in res.tokens]
        seq = np.concatenate([r.prompt, [tok0[rid]], toks[:-1]])
        n = len(seq)
        tokens = np.zeros(pad, np.int32)
        tokens[:n] = seq
        depth = np.zeros(pad, np.int32)
        depth[:n] = L
        head = np.zeros(pad, np.int32)
        target = np.full(pad, -1, np.int32)
        target[r.plen - 1] = tok0[rid]
        exits = []
        for j, tok in enumerate(toks):
            p = r.plen + j
            target[p] = tok
            layer = 0
            if res.exited[j]:
                layer = _exit_layer(system, res.exit_tiers[j])
                depth[p] = system.depth_after_exit(layer)
            head[p] = layer
            exits.append((p, layer))
        out.append({"rid": rid, "tokens": tokens, "depth": depth,
                    "head": head, "target": target, "exits": exits,
                    "n": n})
    return out


def _exit_layer(system: System, tier: int) -> int:
    seg = system.executor.segments[tier]
    if len(seg.branches) != 1:
        raise NotImplementedError("a tier with several exit heads")
    return seg.branches[0]


#: What ``check_outputs`` reads, per reading set (program or stand-in).
READINGS = ("gap_max", "gap_mean", "exit_err_max", "exit_err_mean",
            "exit_mismatch")


def check_outputs(system: System, seqs: list, *, control: bool = False
                  ) -> dict:
    """Compare the served tokens with the float32 reference.

    Per served token, the gap by which its logit lies below the
    reference's best, at the head that produced it (the main head, or the
    exit head it left at; the prefill's first token included):
    ``gap_max`` and ``gap_mean`` over the sampled tokens.  Per decoded
    position, the margin by which the reference's normalized entropy at an
    exit head lies on the other side of the threshold from the served
    decision (exited but above it, or ran on but below it):
    ``exit_err_max`` and ``exit_err_mean``; and ``exit_mismatch``, the
    positions whose served head is not the one the reference decides.
    With ``control``, the same readings, prefixed ``control_``, for the
    control put in the program's place: the reference in float8, its
    tokens its own top token at each position, its exit decisions its
    own, read against the float32 reference."""
    ref_mod = reference_module(system.spec["reference"])
    thr = float(system.cfg.exit_threshold)
    br = system.branches
    stand_ins = {"control_": {"quant": True}} if control else {}
    acc = {p: {"gaps": [], "margins": [], "mismatch": 0}
           for p in ("", *stand_ins)}
    for s in seqs:
        targets, decided = [s["target"]], {"": s["exits"]}
        for prefix, kw in stand_ins.items():
            got = ref_mod.score(system.params, system.spec, s["tokens"],
                                s["depth"], s["head"], s["target"][None],
                                br, **kw)
            targets.append(np.where(s["target"] >= 0, got["argmax"], -1))
            decided[prefix] = [(p, decide(got["entropy"], p, br, thr))
                               for p, _ in s["exits"]]
        ref = ref_mod.score(system.params, system.spec, s["tokens"],
                            s["depth"], s["head"], np.stack(targets), br)
        live = s["target"] >= 0
        for k, prefix in enumerate(acc):
            a = acc[prefix]
            a["gaps"].extend(np.asarray(ref["gap"][k][live], float))
            for p, layer in decided[prefix]:
                a["margins"].append(exit_error(ref["entropy"], [(p, layer)],
                                               br, thr))
                a["mismatch"] += layer != decide(ref["entropy"], p, br, thr)
    out = {"tokens": len(acc[""]["gaps"])}
    for prefix, a in acc.items():
        gaps, margins = a["gaps"] or [0.0], a["margins"] or [0.0]
        out.update({f"{prefix}gap_max": float(max(gaps)),
                    f"{prefix}gap_mean": float(np.mean(gaps)),
                    f"{prefix}exit_err_max": float(max(margins)),
                    f"{prefix}exit_err_mean": float(np.mean(margins)),
                    f"{prefix}exit_mismatch": int(a["mismatch"])})
    return out


def decide(entropy, p: int, branches, thr: float) -> int:
    """The exit head a token at ``p`` leaves at under ``entropy``, 0 if
    none."""
    for j, b in enumerate(branches):
        if entropy[j][p] < thr:
            return b
    return 0


def exit_error(entropy, exits, branches, thr: float) -> float:
    """Widest margin by which ``entropy`` (per head, per position) lies on
    the other side of ``thr`` from the decisions ``[(position, head)]``."""
    worst = 0.0
    for p, layer in exits:
        for j, b in enumerate(branches):
            h = float(entropy[j][p])
            if layer == b:
                worst = max(worst, h - thr)
                break
            worst = max(worst, thr - h)
    return worst


def free_program_state(system: System, *, keep_programs: bool = False
                       ) -> None:
    """Drop the scheduler's caches (and, unless ``keep_programs``, every
    compiled program's handle) so the reference has the chip's memory to
    itself, params aside."""
    sched = system.sched
    sched.caches = None
    sched.tok_dev = None
    if not keep_programs:
        system.executor._fn_cache.clear()
        system.executor._fns = []
    system.prefills = [(c, r, None) for c, r, _ in system.prefills]
    gc.collect()
