"""Serving launcher: LM batch serving *and* the simulation job service.

Two archs families behind one entry point:

* ``--arch <model>`` (e.g. ``gemma-2b``): the original batched
  prefill + decode LM path, unchanged::

      PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \\
          --batch 4 --prompt-len 32 --gen 16

* ``--arch sim``: a long-lived **simulation job server**.  Clients
  POST typed :class:`~repro.runtime.SimJobSpec` JSON; a single worker
  thread multiplexes the queue onto one resident mesh, and every job
  built with the shared ``sim_cache`` reuses the same compiled segment
  function when only seeds differ (``sim_fingerprint`` normalizes
  them out) -- submit ten 3-member ensembles, compile once::

      PYTHONPATH=src python -m repro.launch.serve --arch sim --port 8321

  Endpoints (JSON over HTTP, loopback by default):

  ``POST /v1/sim/jobs``
      Body: ``SimJobSpec`` JSON.  Returns ``{"job_id", "status"}``.
      Malformed/unknown-field specs are a 400 with the validation
      error, not a silent default.
  ``GET /v1/sim/jobs``
      All jobs, queue order.
  ``GET /v1/sim/jobs/<id>``
      One job: status (``queued|running|done|failed``), spec, result
      (final step, rates, per-member plastic digests, compiled-step
      count) or error.
  ``GET /v1/sim/jobs/<id>/stream[?cursor=<json>]``
      Incremental spike readout while the job runs: serves the records
      appended to the job's spool since ``cursor`` (the per-log record
      offsets returned by the previous call -- the same offsets shape
      the exactly-once checkpoint contract uses), grouped per ensemble
      member and step-ordered.  Stateless on the server: each client
      owns its cursor, so any number of clients stream concurrently at
      their own pace.  Returns ``{"streams", "cursor", "status",
      "done"}``; pass ``cursor`` back verbatim to get only deltas.

The server never runs jax in HTTP handler threads -- simulation
happens on the one worker thread (the mesh's owner); handlers only
read spool files, which the append-only/whole-record contract makes
safe under concurrent writes.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import queue
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

import numpy as np

from repro.obs.telemetry import NULL, Telemetry
from repro.runtime.jobs import JobError, SimJobSpec


# --------------------------------------------------------------------------
# LM serving path (unchanged behaviour)
# --------------------------------------------------------------------------

def serve_batch(arch: str, batch: int, prompt_len: int, gen: int,
                mesh=None, seed: int = 0, greedy: bool = True):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.models.transformer import init_decode_state, init_model
    from repro.parallel.sharding import rules_for_mesh

    cfg = get_reduced(arch)
    mesh = mesh or make_host_mesh()
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ov = {}
    if cfg.n_heads % axes.get("model", 1):
        ov["heads"] = None
    if cfg.d_ff % axes.get("model", 1) or not cfg.d_ff:
        ov["mlp"] = None
    if cfg.n_experts and cfg.n_experts % axes.get("model", 1):
        ov["experts"] = None
    max_len = prompt_len + gen
    if max_len % axes.get("model", 1):
        ov["kv_seq"] = None
    if batch % (axes.get("data", 1) * axes.get("pod", 1)):
        ov["batch"] = None
    rules = rules_for_mesh(mesh, **ov)

    key = jax.random.PRNGKey(seed)
    params, _ = init_model(key, cfg)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (batch, prompt_len)).astype(np.int32)
    batch_in = {"tokens": jnp.asarray(prompts)}
    if cfg.encoder_seq:
        from repro.models.frontends import STUB_WIDTH
        batch_in["frames"] = jnp.asarray(rng.standard_normal(
            (batch, cfg.encoder_seq, STUB_WIDTH)), jnp.dtype(cfg.dtype))
    if cfg.n_patches:
        from repro.models.frontends import STUB_WIDTH
        batch_in["patch_embeds"] = jnp.asarray(rng.standard_normal(
            (batch, cfg.n_patches, STUB_WIDTH)), jnp.dtype(cfg.dtype))

    state = init_decode_state(cfg, batch, max_len)
    prefill = jax.jit(M.make_prefill(cfg, rules))
    serve_step = jax.jit(M.make_serve_step(cfg, rules))

    t0 = time.perf_counter()
    logits, state = prefill(params, batch_in, state)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    toks = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.perf_counter()
    for i in range(gen):
        toks.append(np.asarray(tok))
        logits, state = serve_step(params, state, tok,
                                   jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0
    out_tokens = np.concatenate(toks, axis=1)
    return {
        "tokens": out_tokens,
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / gen,
        "config": cfg.name,
    }


# --------------------------------------------------------------------------
# Simulation job service
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SimJob:
    """One queued/running/finished job: the spec plus its lifecycle."""
    job_id: str
    spec: SimJobSpec
    status: str = "queued"        # queued -> running -> done | failed
    error: Optional[str] = None
    result: Optional[dict] = None

    def public(self) -> dict:
        return {"job_id": self.job_id, "status": self.status,
                "spec": self.spec.job_meta(), "error": self.error,
                "result": self.result}


class SimJobServer:
    """Queue of :class:`SimJobSpec` jobs on one resident mesh.

    One worker thread owns the mesh and runs jobs in submission order;
    ``sim_cache`` is shared across every job it builds, so jobs whose
    traced program is identical (same grid/law/tiling/ensemble width,
    any seeds -- see ``sim_fingerprint``) reuse one compiled step.
    ``compiled_steps()`` exposes the cache size: the CI smoke asserts
    it stays 1 across a multi-job ensemble session.
    """

    def __init__(self, mesh=None, telemetry: Telemetry = NULL):
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.mesh = mesh
        self.tel = telemetry
        self.sim_cache: dict = {}
        self._jobs: Dict[str, SimJob] = {}
        self._order = []
        self._queue: "queue.Queue[Optional[str]]" = queue.Queue()
        self._lock = threading.Lock()
        self._n = 0
        self._worker = threading.Thread(target=self._run_jobs,
                                        name="sim-job-worker", daemon=True)
        self._worker.start()

    # ---- submission/introspection (any thread) -----------------------
    def submit(self, spec: SimJobSpec) -> str:
        with self._lock:
            self._n += 1
            job_id = f"job-{self._n:04d}"
            self._jobs[job_id] = SimJob(job_id, spec)
            self._order.append(job_id)
        self._queue.put(job_id)
        return job_id

    def job(self, job_id: str) -> Optional[SimJob]:
        with self._lock:
            return self._jobs.get(job_id)

    def jobs(self):
        with self._lock:
            return [self._jobs[j] for j in self._order]

    def compiled_steps(self) -> int:
        return len(self.sim_cache)

    def wait(self, job_id: str, timeout: float = 600.0) -> SimJob:
        """Block until a job leaves the queue/running states."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            j = self.job(job_id)
            if j is None:
                raise KeyError(job_id)
            if j.status in ("done", "failed"):
                return j
            time.sleep(0.05)
        raise TimeoutError(f"{job_id} still {self.job(job_id).status} "
                           f"after {timeout}s")

    def shutdown(self):
        self._queue.put(None)
        self._worker.join(timeout=60)

    # ---- the worker thread: owns the mesh and all jax work -----------
    def _run_jobs(self):
        from repro.runtime.jobs import build_sim_driver
        while True:
            job_id = self._queue.get()
            if job_id is None:
                return
            job = self.job(job_id)
            job.status = "running"
            try:
                driver = build_sim_driver(job.spec, mesh=self.mesh,
                                          telemetry=self.tel,
                                          sim_cache=self.sim_cache)
                out = driver.run(job.spec.t_steps)
                job.result = self._summarize(driver, out)
                job.status = "done"
            except Exception as e:           # a bad job must not kill
                job.error = f"{type(e).__name__}: {e}"   # the server
                job.status = "failed"

    def _summarize(self, driver, out) -> dict:
        state = out["state"]
        res = {
            "final_step": int(np.max(np.asarray(state["t"]))),
            "preempted": bool(out["preempted"]),
            "rate_hz": float(driver.firing_rate_hz(state)),
            "totals": driver.metric_totals(state),
            "n_synapses": int(driver.table_stats["n_synapses"]),
            "members": driver.n_members,
            "compiled_steps": driver.compiled_step_cache_size(),
            "server_compiled_steps": self.compiled_steps(),
        }
        if driver.spool is not None:
            res["spool_dir"] = driver.spool.directory
            res["spooled_events"] = sum(driver.spool.offsets().values())
        if driver.plastic:
            if driver.n_members is None:
                res["plastic"] = driver.plastic_summary(state)
            else:
                res["plastic_members"] = [
                    driver.plastic_summary(state, member=m)
                    for m in range(driver.n_members)]
        return res

    # ---- streaming read side (HTTP handler threads) ------------------
    def stream(self, job_id: str,
               cursor: Optional[Dict[str, int]] = None) -> dict:
        """Spool records appended since ``cursor``, grouped per member.

        Purely file-backed -- no lock against the worker is needed
        because the logs are append-only and ``read_new_events`` reads
        whole records below the current file size only.
        """
        from repro.obs.spool import read_new_events
        job = self.job(job_id)
        if job is None:
            raise KeyError(job_id)
        if not job.spec.record:
            raise JobError(f"{job_id} was submitted with record=false; "
                           "there is no spike stream to read")
        try:
            new, new_cursor = read_new_events(job.spec.ckpt_dir, cursor)
        except FileNotFoundError:
            # queued job whose spool does not exist yet: empty delta
            new, new_cursor = {}, dict(cursor or {})
        streams: Dict[str, dict] = {}
        for rel, arr in new.items():
            member = rel.split("/", 1)[0] if "/" in rel else "solo"
            g = streams.setdefault(member, {"step": [], "gid": []})
            g["step"].append(arr["step"])
            g["gid"].append(arr["gid"])
        for member, g in streams.items():
            step = np.concatenate(g["step"])
            gid = np.concatenate(g["gid"])
            order = np.lexsort((gid, step))
            streams[member] = {"step": step[order].tolist(),
                               "gid": gid[order].tolist(),
                               "n_new": int(step.size)}
        return {"job_id": job_id, "status": job.status,
                "done": job.status in ("done", "failed"),
                "streams": streams, "cursor": new_cursor}


def _make_handler(server: SimJobServer):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):        # quiet; telemetry has spans
            pass

        def _send(self, code: int, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            url = urllib.parse.urlparse(self.path)
            if url.path != "/v1/sim/jobs":
                return self._send(404, {"error": f"no route {url.path}"})
            n = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(n).decode()
            try:
                spec = SimJobSpec.from_json(raw)
            except (ValueError, TypeError) as e:
                return self._send(400, {"error": str(e)})
            job_id = server.submit(spec)
            self._send(200, {"job_id": job_id, "status": "queued"})

        def do_GET(self):
            url = urllib.parse.urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            if parts[:3] != ["v1", "sim", "jobs"]:
                return self._send(404, {"error": f"no route {url.path}"})
            if len(parts) == 3:
                return self._send(200, {"jobs": [j.public()
                                                 for j in server.jobs()]})
            job_id = parts[3]
            if server.job(job_id) is None:
                return self._send(404, {"error": f"unknown job {job_id}"})
            if len(parts) == 4:
                return self._send(200, server.job(job_id).public())
            if len(parts) == 5 and parts[4] == "stream":
                q = urllib.parse.parse_qs(url.query)
                cursor = None
                if "cursor" in q:
                    try:
                        cursor = json.loads(q["cursor"][0])
                    except ValueError as e:
                        return self._send(400, {"error": f"cursor: {e}"})
                try:
                    return self._send(200, server.stream(job_id, cursor))
                except JobError as e:
                    return self._send(400, {"error": str(e)})
            self._send(404, {"error": f"no route {url.path}"})

    return Handler


def serve_sim(host: str = "127.0.0.1", port: int = 0, mesh=None,
              telemetry: Telemetry = NULL):
    """Start the job server + its HTTP front.  Returns ``(httpd,
    jobs)``; the HTTP server runs on a daemon thread, ``httpd.shutdown()``
    then ``jobs.shutdown()`` stops both."""
    jobs = SimJobServer(mesh=mesh, telemetry=telemetry)
    httpd = ThreadingHTTPServer((host, port), _make_handler(jobs))
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever, name="sim-http",
                         daemon=True)
    t.start()
    return httpd, jobs


def main(argv=None):
    from repro.configs import ARCH_NAMES
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="gemma-2b",
                    help="'sim' for the simulation job server, or an "
                         "LM arch name")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --arch sim (loopback only "
                         "by default; the service is unauthenticated)")
    ap.add_argument("--port", type=int, default=8321,
                    help="port for --arch sim (0 picks a free one)")
    args = ap.parse_args(argv)

    choices = ("sim",) + tuple(ARCH_NAMES)
    if args.arch not in choices:
        # an unknown arch used to die with a bare KeyError from the
        # config registry -- be explicit, and list what would work
        raise SystemExit(
            f"--arch {args.arch!r}: unknown arch; choices: "
            + ", ".join(choices))
    enable_compile_cache()
    if args.arch == "sim":
        httpd, jobs = serve_sim(args.host, args.port)
        host, port = httpd.server_address[:2]
        print(f"sim job server on http://{host}:{port} "
              f"(POST /v1/sim/jobs)", flush=True)
        try:
            threading.Event().wait()
        except KeyboardInterrupt:
            httpd.shutdown()
            jobs.shutdown()
        return
    out = serve_batch(args.arch, args.batch, args.prompt_len, args.gen)
    print(f"{out['config']}: prefill {out['prefill_s']*1e3:.1f} ms, "
          f"decode {out['decode_s_per_token']*1e3:.2f} ms/token, "
          f"generated shape {out['tokens'].shape}")


if __name__ == "__main__":
    main()
