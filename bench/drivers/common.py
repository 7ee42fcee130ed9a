"""What every driver shares: the run's context, the program's model
configuration from a configuration file, compile counting and the traced
stretch of the window."""

from __future__ import annotations

import dataclasses
import gc
import os
import resource
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax

from bench import trace as trace_mod

# Published configuration keys -> the program's ModelConfig fields.
_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
         "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
         "torch_dtype": "dtype"}
# The program's norms hard-code these epsilons (repro.nn.layers).
_PROGRAM_EPS = {"rms_norm_eps": ("rmsnorm", 1e-6),
                "layer_norm_eps": ("layernorm", 1e-5)}


@dataclasses.dataclass
class Context:
    """One run: the cell's configuration file and traffic mix, the
    arguments, the devices JAX found and the process's start time."""
    config: Dict
    mix: Dict
    seed: int
    seconds: float
    trace: bool
    devices: List
    t0: float
    peaks: Dict[str, float]


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def program_config(c: Dict):
    """The program's ModelConfig for a configuration file: its named base
    configuration with every published key the file states."""
    from repro.configs import get_config
    kw = {field: c[key] for key, field in _KEYS.items() if key in c}
    kw["head_dim"] = int(c.get("head_dim") or c["hidden_size"]
                         // c["num_attention_heads"])
    kw["qkv_bias"] = bool(c.get("attention_bias", c.get("use_qkv_bias")))
    for key, (norm, eps) in _PROGRAM_EPS.items():
        if key in c:
            if float(c[key]) != eps:
                raise ValueError(f"{key}={c[key]}: the program's {norm} uses "
                                 f"{eps}")
            kw["norm"] = norm
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the program's "
                         "gated MLP is SwiGLU")
    if float(c.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the program rotates every head dim "
                         "(partial_rotary_factor 1.0)")
    kw["mlp"] = "swiglu"
    return get_config(c["program_config"]).replace(**kw)


class CompileCounter:
    """Counts executables JAX builds (from its cache or by compiling) while
    ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        from jax._src import dispatch
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, _secs, **_kw):
            if self.active and name == event:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


class GcClock:
    """Times Python's garbage collections while ``active``: a pause of the
    host loop that no device trace shows."""

    def __init__(self):
        self.active = False
        self.count = self.full = 0
        self.seconds = self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._note)

    def _note(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self.active and self._t is not None:
            d = time.monotonic() - self._t
            self.count += 1
            self.full += info.get("generation") == 2
            self.seconds += d
            self.longest = max(self.longest, d)


class HostClock:
    """What the host did to this process over the window: the process's
    CPU seconds, its context switches (involuntary ones are the kernel
    taking the CPU away, as other work on a shared host does) and the
    host's load average at both ends."""

    def start(self) -> None:
        self._t = time.monotonic()
        self._cpu = time.process_time()
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        self._load = os.getloadavg()[0]

    def stop(self) -> None:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.wall = time.monotonic() - self._t
        self.cpu = time.process_time() - self._cpu
        self.involuntary = ru.ru_nivcsw - self._ru.ru_nivcsw
        self.voluntary = ru.ru_nvcsw - self._ru.ru_nvcsw
        self.load = (self._load, os.getloadavg()[0])

    def __str__(self) -> str:
        return (f"process CPU {self.cpu:.2f}s of {self.wall:.2f}s, "
                f"{self.involuntary} involuntary and {self.voluntary} "
                f"voluntary context switches, load average "
                f"{self.load[0]:.2f} -> {self.load[1]:.2f} on "
                f"{os.cpu_count()} CPUs")


class Tracer:
    """Traces the window from ``start`` seconds after its start to its
    close, inside the host span ``bench.trace_window``.  The profiler is
    stopped, and its trace written, only after the window has closed, so
    that writing it delays nothing in the window.  ``poll(now)`` is called
    from the driver's loop."""

    def __init__(self, enabled: bool, start: float):
        self.enabled = enabled
        self.start = start
        self.dir: Optional[str] = None
        self._span = None
        self.state = "idle"

    def poll(self, rel: float) -> None:
        if self.enabled and self.state == "idle" and rel >= self.start:
            t = time.monotonic()
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.host_tracer_level = 1       # the benchmark's own spans
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self._span.__enter__()
            self.state = "on"
            log(f"profiler started in {time.monotonic() - t:.2f}s")

    def reduce(self) -> Optional[Dict]:
        """Stop the profiler and reduce its trace (None without one)."""
        if self.state != "on":
            return None
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        try:
            path = trace_mod.find_xplane(self.dir)
            if path is None:
                print("[bench] the profiler wrote no trace", file=sys.stderr)
                return None
            t = time.monotonic()
            out = trace_mod.reduce(trace_mod.Events.from_file(path))
            log(f"trace reduced in {time.monotonic() - t:.1f}s")
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
