"""TaskExecutor: in-container bootstrap.

Rebuild of the reference's ``TaskExecutor`` (SURVEY.md sections 2, 3.2 — the
contract this must replicate): read the AM-injected env; reserve a data port;
register ``(jobName, index, host:port)`` with the AM; block for the cluster
spec (gang barrier); let the framework runtime translate the spec into env;
exec the user process; heartbeat + metrics loops; propagate the exit code
faithfully.

Launched by the AM inside each container as
``python -m tony_tpu.executor.task_executor`` with TONY_* env set.
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import threading
import time

import grpc

from tony_tpu.chaos import chaos_hook
from tony_tpu.obs import trace
from tony_tpu.config.config import TonyConfig
from tony_tpu.config.keys import Keys
from tony_tpu.rpc import ApplicationRpcClient, pb
from tony_tpu.runtime import TaskIdentity, make_runtime
from tony_tpu.utils.net import find_free_port, local_host
from tony_tpu.utils.proc import run_logged

log = logging.getLogger(__name__)

# Exit code when the AM tells us to abort (stale attempt / job teardown);
# mirrors 128+SIGTERM so it reads like a kill in status output.
ABORT_EXIT_CODE = 143


class TaskExecutor:
    def __init__(self) -> None:
        self.job_name = os.environ["TONY_JOB_NAME"]
        self.index = int(os.environ["TONY_TASK_INDEX"])
        self.attempt = int(os.environ.get("TONY_ATTEMPT", "0"))
        self.am_addr = os.environ["TONY_AM_ADDR"]
        self.container_id = os.environ.get("TONY_CONTAINER_ID", "")
        conf_path = os.environ["TONY_CONF_PATH"]
        self.config = TonyConfig.from_json(open(conf_path).read())
        self.spec = self.config.task_spec(self.job_name)
        self.runtime = make_runtime(
            self.config.get_str(Keys.APPLICATION_FRAMEWORK, "jax")
        )
        token = None
        if self.config.get_bool(Keys.APPLICATION_SECURITY_ENABLED, False):
            from tony_tpu.rpc.auth import read_token

            token = read_token(os.environ.get("TONY_APP_DIR", ""))
        self.client = ApplicationRpcClient(self.am_addr, token=token)
        self.host = local_host()
        self.port = find_free_port() if self.runtime.needs_data_port() else 0
        self._abort = threading.Event()
        self._child = None

    # --- bootstrap ----------------------------------------------------------

    def register(self, timeout_s: float = 60.0) -> None:
        """Register with the AM, retrying while its RPC server comes up."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                resp = self.client.register_worker_spec(
                    self.job_name,
                    self.index,
                    self.host,
                    self.port,
                    attempt=self.attempt,
                    container_id=self.container_id,
                )
                if not resp.accepted:
                    raise SystemExit(
                        f"AM rejected registration: {resp.message} (stale attempt?)"
                    )
                return
            except grpc.RpcError as e:
                if time.monotonic() > deadline:
                    raise SystemExit(f"cannot reach AM at {self.am_addr}: {e}") from e
                time.sleep(0.5)

    def await_cluster_spec(self) -> TaskIdentity:
        """Poll GetClusterSpec until the gang barrier opens."""
        timeout_s = self.config.get_float(Keys.TASK_REGISTRATION_TIMEOUT_S, 300.0)
        deadline = time.monotonic() + timeout_s
        while True:
            if self._abort.is_set():
                raise SystemExit(ABORT_EXIT_CODE)
            resp = self.client.get_cluster_spec(self.job_name, self.index, self.attempt)
            if resp.ready:
                return TaskIdentity.from_cluster_spec_response(
                    self.job_name, self.index, resp
                )
            if time.monotonic() > deadline:
                raise SystemExit(
                    f"cluster spec not ready after {timeout_s}s (gang barrier)"
                )
            time.sleep(0.3)

    # --- supervision threads -------------------------------------------------

    def _heartbeat_loop(self) -> None:
        interval = self.config.get_int(Keys.TASK_HEARTBEAT_INTERVAL_MS, 1000) / 1000
        while not self._abort.is_set():
            # chaos seam: kill_container SIGKILLs this process group here
            # (the count is this executor's heartbeat number — "at beat N"
            # is exact); drop_heartbeats returns a suppression and the
            # beat is skipped while the user process keeps running
            if chaos_hook(
                "executor.beat",
                task=f"{self.job_name}:{self.index}",
                attempt=self.attempt,
            ):
                time.sleep(interval)
                continue
            try:
                resp = self.client.heartbeat(self.job_name, self.index, self.attempt)
                if resp.action == pb.HeartbeatResponse.ABORT:
                    log.warning("AM ordered abort; killing user process")
                    trace.instant(
                        "executor.abort", task=f"{self.job_name}:{self.index}"
                    )
                    self._abort.set()
                    break
            except grpc.RpcError:
                # AM temporarily unreachable: keep trying; the AM's own
                # missed-heartbeat accounting decides when we are lost.
                pass
            time.sleep(interval)

    def _metrics_loop(self) -> None:
        if not self.config.get_bool(Keys.METRICS_ENABLED, True):
            return
        from tony_tpu.obs.monitor import TaskMonitor

        interval = self.config.get_int(Keys.METRICS_INTERVAL_MS, 2000) / 1000
        monitor = TaskMonitor()
        while not self._abort.is_set():
            time.sleep(interval)
            try:
                samples = monitor.sample()
                if samples:
                    self.client.push_metrics(self.job_name, self.index, samples)
            except grpc.RpcError:
                pass
            except Exception:
                log.exception("metrics sampling failed")
                return

    # --- main ----------------------------------------------------------------

    def run(self) -> int:
        with trace.span("executor.register",
                        task=f"{self.job_name}:{self.index}"):
            self.register()
        log.info(
            "%s:%d registered at %s:%d (attempt %d); awaiting cluster spec",
            self.job_name, self.index, self.host, self.port, self.attempt,
        )
        # Heartbeat from the moment we are registered (the reference starts
        # its heartbeat right after registration too) — a gang that takes a
        # while to assemble must not look heartbeat-dead to the AM.
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True, name="heartbeat")
        hb.start()
        with trace.span("executor.await_cluster_spec",
                        task=f"{self.job_name}:{self.index}"):
            identity = self.await_cluster_spec()
        env = self.runtime.build_env(identity, self.config)
        env["TONY_APP_ID"] = os.environ.get("TONY_APP_ID", "")
        env["TONY_APP_DIR"] = os.environ.get("TONY_APP_DIR", "")
        env["TONY_EXECUTOR_PID"] = str(os.getpid())
        command = self.spec.command
        if not command:
            raise SystemExit(f"job.{self.job_name}.command is empty")
        # Run in the staged source dir (the HDFS src_dir localisation analogue,
        # SURVEY.md section 3.1: client stages src zip -> containers unpack).
        src_dir = os.path.join(os.environ.get("TONY_APP_DIR", ""), "src")
        cwd = src_dir if os.path.isdir(src_dir) else None
        log.info("starting user process: %s (cwd=%s)", command, cwd or ".")
        # the user process joins the trace under its own journal name,
        # rooted on this span (fit()/the engine call trace.install_from_env)
        user_span = trace.span(
            "executor.user_process",
            task=f"{self.job_name}:{self.index}", attempt=self.attempt,
        )
        if trace.active_tracer() is not None:
            env[trace.ENV_PROC] = (
                f"{self.job_name}_{self.index}_user_a{self.attempt}"
            )
            env[trace.ENV_PARENT] = user_span.sid
        self._child = run_logged(command, env=env, cwd=cwd)

        mt = threading.Thread(target=self._metrics_loop, daemon=True, name="metrics")
        mt.start()

        # Forward SIGTERM (container release) to the child so user cleanup runs.
        signal.signal(signal.SIGTERM, lambda *_: self._abort.set())

        while True:
            code = self._child.poll()
            if code is not None:
                self._child.wait()  # drain log pump
                break
            if self._abort.is_set():
                self._child.terminate()
                try:
                    code = self._child.wait(timeout=5)
                except Exception:
                    self._child.kill()
                    code = ABORT_EXIT_CODE
                code = ABORT_EXIT_CODE
                break
            time.sleep(0.2)

        log.info("user process exited with code %d", code)
        user_span.end(exit_code=code)
        self._abort.set()
        try:
            self.client.register_execution_result(
                self.job_name, self.index, code, attempt=self.attempt
            )
        except grpc.RpcError as e:
            # AM may already be tearing down; the container exit code still
            # carries the result (AM's backup path).
            log.warning("could not report result to AM: %s", e)
        self.client.close()
        return code


def main() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s EXEC %(levelname)s %(name)s: %(message)s",
    )
    executor = TaskExecutor()
    # arm fault injection for THIS executor only when the job asks for it
    from tony_tpu.chaos import install_from_config

    install_from_config(executor.config, role="executor")
    # join the trace spine from the AM-exported env (no-op when untraced)
    trace.install_from_env()
    code = executor.run()
    trace.uninstall()  # flush + close the journal before exit
    sys.exit(code)


if __name__ == "__main__":
    main()
