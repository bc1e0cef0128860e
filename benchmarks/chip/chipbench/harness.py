"""One run of one cell: set-up, the measured window, the comparison, and
the result line.

Set-up makes the weights on the device from the seed in one jitted call,
builds a ``PagedEngine`` on them, warms the decode step and the prefill
of each of the cell's page buckets (and nothing else), and builds the
governed agent over that engine. The window then serves the cell's
traffic for ``seconds``; nothing may compile inside it. After the window
the device's peak memory is read, the program's state is freed, and the
finished requests are compared with the plain reference.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.model import Model
from repro.models.params import split_params
from repro.serving.engine import PagedEngine

from . import check, measures, serve, spec, weights, xtrace
from . import traffic as T

DRAIN_S = 60.0  # after the window, how long every due request may wait
OUT_DIR = ".bench_out"     # under the checkout: traces, then removed
ITL_QS = (50, 90, 93, 95, 97, 99)   # the gaps' shape around their tail


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(cell: str, msg: str) -> None:
    print(f"[{cell}] {msg}", flush=True)


def device_check(cell: spec.Cell, require_tpu: bool) -> List[Any]:
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} "
                     f"device(s): nothing was run")
    return devices[:cell.chips]


def warm(engine: PagedEngine, traffic: Dict[str, Any]) -> None:
    """Compile, or load from the cache, every program the cell's traffic
    drives: the prefill and its page writes for each page bucket, and the
    decode step."""
    page = traffic["page_size"]
    for b in traffic["prompt"]["page_buckets"]:
        engine.admit(f"warm-{b}", np.ones(b * page - 1, np.int32), 2)
        while engine.n_inflight:
            engine.step()


class Bench:
    """Set-up of one cell for one seed: weights, engine, warm-up."""

    def __init__(self, cell: spec.Cell, seed: int, *,
                 require_tpu: bool = True):
        self.cell, self.seed = cell, seed
        self.devices = device_check(cell, require_tpu)
        # the chip's peaks; off the chip (tests) there are none to use
        self.peaks = (spec.load_peaks(self.devices[0].device_kind)
                      if require_tpu else None)
        self.arch = ArchConfig(**cell.config)
        abstract = split_params(
            Model(self.arch, dtype=jnp.float32).abstract_params())[0]
        self.prog_w, self.pub_w = weights.make(abstract, cell.config, seed)
        jax.block_until_ready((self.prog_w, self.pub_w))
        tr = cell.traffic
        self.engine = PagedEngine(
            self.arch, max_batch=tr["lanes"], num_pages=T.pool_pages(tr),
            page_size=tr["page_size"], params=self.prog_w,
            max_pages_per_seq=T.max_pages_per_seq(tr))
        warm(self.engine, tr)
        self.spans = serve.Spans()
        serve.instrument(self.engine, self.spans)

    def serve(self, reqs: List[T.Request], seconds: float,
              counter: serve.CompileCounter,
              trace_dir: Optional[Path] = None) -> serve.Run:
        agent = serve.build_agent(self.arch, self.engine, self.cell.traffic,
                                  self.spans)
        on_open = None
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            on_open = lambda: jax.profiler.start_trace(  # noqa: E731
                str(trace_dir), profiler_options=opts)
        try:
            run = serve.serve_window(agent, reqs, seconds, self.spans,
                                     counter, DRAIN_S, on_open=on_open)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        run.cfg, run.traffic = self.cell.config, self.cell.traffic
        run.peaks = self.peaks
        return run

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def free_program(self) -> None:
        """Drop the engine, its arenas and its compiled programs; the
        published-form weights stay for the reference."""
        self.engine.pool.k = self.engine.pool.v = None
        self.engine.params = None
        self.engine = self.prog_w = None
        gc.collect()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = spec.ROOT, require_tpu: bool = True,
             control: bool = False) -> Dict[str, Any]:
    """Everything after argument parsing; returns the result line."""
    with serve.CompileCounter() as counter:
        bench = Bench(cell, seed, require_tpu=require_tpu)
        dev = bench.devices[0]
        tr = cell.traffic
        reqs = T.generate(tr, seed, seconds, cell.config["vocab"])
        say(cell.name, f"{dev.platform} {dev.device_kind} x{len(jax.devices())}"
                       f"; {cell.config_name} {cell.config['n_layers']} layers "
                       f"d_model {cell.config['d_model']}; {tr['lanes']} lanes,"
                       f" {T.pool_pages(tr)} pages of {tr['page_size']}, "
                       f"{T.max_pages_per_seq(tr)} pages/seq; "
                       f"{T.describe(reqs)}; {tr['rate_rps']} req/s")
        trace_dir = None
        if trace:
            trace_dir = Path(root) / OUT_DIR / f"trace-{cell.name}"
            shutil.rmtree(trace_dir, ignore_errors=True)
        run = bench.serve(reqs, seconds, counter, trace_dir)
    run.setup_s = run.t0 - t_start
    peak = bench.peak_bytes()
    pages = [s["pages"] for s in measures.spans_in_window(run, "decode")]
    if trace_dir is not None:
        ex = xtrace.extract(xtrace.find_xplane(trace_dir))
        run.trace = {**ex, **xtrace.reduce(ex)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    say(cell.name, f"setup_s {run.setup_s}; compiles by phase "
                   f"{dict(counter.compiles)} taking "
                   f"{dict(counter.seconds)} s; persistent cache "
                   f"{dict(counter.events)}; steps {len(run.steps)}; "
                   f"generator late by at most "
                   f"{max(run.lateness_s, default=0.0)} s; full collections "
                   f"in the window {len(run.gc_pauses_s)} taking at most "
                   f"{max(run.gc_pauses_s, default=0.0)} s; pool pages "
                   f"in use at most {max(pages, default=0)} of "
                   f"{T.pool_pages(tr) - 1}, mean {measures.mean(pages)}; "
                   f"memory_peak_bytes {peak}")
    itl = measures.itl_gaps_s(run)
    prefills = Counter(len(s["admitted"]) for s in run.steps
                       if measures.in_window(run, s["result_ts"]))
    say(cell.name, f"inter-token gaps {len(itl)}, ms at p50/p90/p93/p95/"
                   f"p97/p99 {[measures.percentile(itl, q) for q in ITL_QS]};"
                   f" steps in the window by requests they prefilled "
                   f"{dict(sorted(prefills.items()))}")
    bench.free_program()

    problems = check.log_problems(run, cell.config["vocab"])
    picks = check.sample(run, seed, tr["check"]["tokens"],
                         tr["check"]["max_requests"])
    prompts = [reqs[i].prompt for i in picks]
    served = [run.requests[i]["tokens"] for i in picks]
    t0 = time.time()
    cmp = check.compare(cell.reference, bench.pub_w, cell.config, tr,
                        prompts, served, cell.config_file["dot_precision"],
                        control=control)
    gaps = {g: cmp[g] for g in check.GAPS}
    say(cell.name, f"reference over {cmp['compared_requests']} requests, "
                   f"{cmp['compared_tokens']} tokens in "
                   f"{time.time() - t0} s; gaps {gaps}")
    limits = cell.limits
    checks: Dict[str, Dict[str, Any]] = {
        g: {"value": cmp[g]["program"], "limit": limits[g]["limit"]}
        for g in check.GAPS if g in limits}
    checks["compared_tokens"] = {
        "value": cmp["compared_tokens"],
        "limit": limits["compared_tokens"]["at_least"], "at_least": True}
    for k, v in problems.items():
        checks[k] = {"value": v, "limit": 0}
    correct = all(c["value"] >= c["limit"] if c.get("at_least")
                  else c["value"] <= c["limit"] for c in checks.values())

    line_metrics = cell.per_layer if trace else cell.end_to_end
    metrics, also = {}, {}
    for m in cell.end_to_end + cell.per_layer + cell.others:
        v = m.read(run)
        if v is None:
            continue
        if m in line_metrics:
            metrics[m.name] = {"value": v, "unit": m.unit}
        else:
            also[m.name] = v
    # what this run could read besides its line: the end-to-end numbers
    # of a traced run (against an untraced one, the tracing's cost), the
    # host-clock layer numbers of an untraced one, and metrics that are
    # not the cell's own (a tail over too few requests)
    say(cell.name, f"also read {also}; {len(run.requests)} requests due")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": len(run.requests),
        "failed": problems["requests_not_admitted"]
        + problems["requests_refused"] + problems["failed_results"],
        "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
        say(cell.name, f"idle by label {run.trace['idle_by_label']}")
    if control:
        line["control"] = {g: cmp[g]["control"] for g in check.GAPS}
        line["program"] = {g: cmp[g]["program"] for g in check.GAPS}
        # the control in the program's place, held to the same limits
        line["control_correct"] = all(
            cmp[g]["control"] <= limits[g]["limit"]
            for g in check.GAPS if g in limits)
    line["checks"] = checks
    for f in run.failed_results[:3]:
        print(f, file=sys.stderr)
    return line


def print_checks(line: Dict[str, Any]) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in line["checks"].items():
        rel = "at least" if c.get("at_least") else "at most"
        print(f"check {name}: {c['value']} ({rel} {c['limit']})",
              file=sys.stderr, flush=True)
