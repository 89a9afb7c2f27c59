"""Cluster launcher: submit a training callable to Slurm or spawn a local
multi-process group.

(reference: dinov3_jax/run/submit.py — a submitit-based Slurm launcher
that was dead code because it imported nonexistent ``utils.cluster`` /
``utils.custom_callable`` modules (SURVEY.md §2.8). This is the working
TPU-native equivalent, with no submitit dependency:

- ``build_sbatch_script`` renders a self-contained sbatch file. One Slurm
  task per host; each task derives ``JAX_PROCESS_ID`` / coordinator env
  from Slurm variables so ``parallel.initialize_distributed`` forms the
  global mesh. ``#SBATCH --requeue`` + ``--signal=TERM@<grace>`` give the
  train loop's PreemptionHandler (run/preemption.py) a grace window to
  checkpoint before the job is requeued — the behavior the reference's
  ``CheckpointableSubmitter.checkpoint`` (:140-145) intended.
- ``LocalLauncher`` spawns N coordinated local processes (CPU backend)
  for multi-process smoke tests without a cluster — the capability the
  reference simulated with 8 virtual devices in one process.
- ``load_callable`` replaces the missing ``custom_callable`` module.)
"""

from __future__ import annotations

import argparse
import importlib.util
import logging
import os
import shlex
import subprocess
import sys
from pathlib import Path
from typing import Callable, Optional, Sequence

logger = logging.getLogger("dinov3")


def load_callable(module_path: str, callable_name: str = "main") -> Callable:
    """Load ``callable_name`` from the Python file at ``module_path``."""
    module_path = os.path.realpath(module_path)
    spec = importlib.util.spec_from_file_location("_dinov3_submitted", module_path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load module from {module_path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    fn = getattr(module, callable_name, None)
    if not callable(fn):
        raise AttributeError(f"{module_path} has no callable {callable_name!r}")
    return fn


def _make_shim(module_path: str, callable_name: str, prologue: str = "") -> str:
    """The inline child program every launch path runs: optional env
    prologue -> multi-host init -> load and invoke the target callable."""
    return (
        "import sys; "
        + prologue
        + "from dinov3_tpu.run.submit import load_callable; "
        "from dinov3_tpu.parallel import initialize_distributed; "
        "initialize_distributed(); "
        f"load_callable({os.path.realpath(module_path)!r}, "
        f"{callable_name!r})(sys.argv[1:])"
    )


def build_sbatch_script(
    *,
    module_path: str,
    script_args: Sequence[str],
    output_dir: str,
    job_name: str = "dinov3",
    nodes: int = 1,
    tasks_per_node: int = 1,
    cpus_per_task: int = 8,
    timeout_min: int = 2800,
    partition: Optional[str] = None,
    account: Optional[str] = None,
    qos: Optional[str] = None,
    nice: int = 0,
    comment: str = "",
    exclude: str = "",
    signal_grace_s: int = 120,
    callable_name: str = "main",
    extra_env: Optional[dict] = None,
) -> str:
    """Render a self-contained sbatch script.

    One task per host (TPU VMs own all local chips per process); the
    inline Python shim maps Slurm env → JAX multi-host env and invokes the
    target callable, so the submitted file needs no wrapper on shared
    storage.
    """
    lines = [
        "#!/bin/bash",
        f"#SBATCH --job-name={job_name}",
        f"#SBATCH --nodes={nodes}",
        f"#SBATCH --ntasks-per-node={tasks_per_node}",
        f"#SBATCH --cpus-per-task={cpus_per_task}",
        f"#SBATCH --time={timeout_min}",
        f"#SBATCH --output={output_dir}/slurm-%j.out",
        f"#SBATCH --error={output_dir}/slurm-%j.err",
        "#SBATCH --requeue",
        f"#SBATCH --signal=TERM@{signal_grace_s}",
    ]
    if partition:
        lines.append(f"#SBATCH --partition={partition}")
    if account:
        lines.append(f"#SBATCH --account={account}")
    if qos:
        lines.append(f"#SBATCH --qos={qos}")
    if nice:
        lines.append(f"#SBATCH --nice={nice}")
    if comment:
        lines.append(f"#SBATCH --comment={shlex.quote(comment)}")
    if exclude:
        lines.append(f"#SBATCH --exclude={exclude}")
    lines.append("")
    for key, value in (extra_env or {}).items():
        lines.append(f"export {key}={shlex.quote(str(value))}")
    # the shim maps per-task Slurm env -> JAX multi-host env itself, so the
    # srun line needs no nested bash -c quoting (script args stay intact
    # whatever characters they contain)
    shim = _make_shim(
        module_path, callable_name,
        prologue=("import os; os.environ.setdefault("
                  "'JAX_PROCESS_ID', os.environ['SLURM_PROCID']); "),
    )
    args = " ".join(shlex.quote(a) for a in script_args)
    lines += [
        "# first task on the first node is the JAX coordinator; port is",
        "# derived from the job id so co-scheduled / requeued jobs on the",
        "# same head node cannot join each other's rendezvous",
        'head_node=$(scontrol show hostnames "$SLURM_JOB_NODELIST" | head -n1)',
        "coord_port=$((12000 + SLURM_JOB_ID % 2000))",
        "export JAX_COORDINATOR_ADDRESS=${head_node}:${coord_port}",
        "export JAX_NUM_PROCESSES=$SLURM_NTASKS",
        f"srun --kill-on-bad-exit=1 {shlex.quote(sys.executable)} "
        f"-c {shlex.quote(shim)} {args}",
        "",
    ]
    return "\n".join(lines)


def submit_job(script: str, output_dir: str) -> Optional[str]:
    """Write the sbatch script under ``output_dir`` and submit it.

    Returns the job id, or None when ``sbatch`` is unavailable (the script
    is still written, for manual submission)."""
    out = Path(output_dir)
    out.mkdir(parents=True, exist_ok=True)
    script_path = out / "job.sbatch"
    script_path.write_text(script)
    try:
        proc = subprocess.run(
            ["sbatch", "--parsable", str(script_path)],
            capture_output=True, text=True, check=True,
        )
    except FileNotFoundError:
        logger.warning("sbatch not on PATH; script left at %s for manual "
                       "submission", script_path)
        return None
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"sbatch rejected the job (exit {e.returncode}): "
            f"{(e.stderr or e.stdout or '').strip()}"
        ) from e
    job_id = proc.stdout.strip().split(";")[0]
    logger.info("submitted job %s; logs under %s", job_id, output_dir)
    return job_id


class LocalLauncher:
    """Spawn ``num_processes`` coordinated local processes (CPU backend).

    Each child gets ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` plus ``JAX_PLATFORMS=cpu``, so
    ``initialize_distributed`` forms a real multi-process group — the
    multi-host code path, minus the cluster."""

    def __init__(self, num_processes: int, port: int = 12321,
                 devices_per_process: int = 1):
        self.num_processes = num_processes
        self.port = port
        self.devices_per_process = devices_per_process

    def launch(self, module_path: str, script_args: Sequence[str] = (),
               callable_name: str = "main", timeout_s: float = 600.0) -> None:
        shim = _make_shim(module_path, callable_name)
        # a CPU tool: the parent may hold the host's chips (or none), and
        # a chip belongs to one process at a time, so every child is
        # pinned to JAX_PLATFORMS=cpu. Package root on PYTHONPATH so
        # children import this framework from any cwd; the host's
        # accelerator/cluster variables (TPU_*, MEGASCALE_*) are dropped
        # so nothing in a child goes looking for a pod
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        procs = []
        for pid in range(self.num_processes):
            env = {
                k: v for k, v in os.environ.items()
                if not k.startswith(("TPU_", "MEGASCALE_"))
                and k != "PYTHONPATH"
            }
            env.update(
                PYTHONPATH=pkg_root,
                JAX_PLATFORMS="cpu",
                JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{self.port}",
                JAX_NUM_PROCESSES=str(self.num_processes),
                JAX_PROCESS_ID=str(pid),
                XLA_FLAGS=(
                    f"--xla_force_host_platform_device_count="
                    f"{self.devices_per_process}"
                ),
            )
            procs.append(subprocess.Popen(
                [sys.executable, "-c", shim, *script_args], env=env,
            ))
        import time as _time

        # poll rather than wait sequentially: one child dying (import
        # error, assert) leaves the rest blocked in collectives on a dead
        # coordinator — fail fast and kill the group
        deadline = _time.monotonic() + timeout_s
        failed = []
        while _time.monotonic() < deadline:
            exits = {pid: proc.poll() for pid, proc in enumerate(procs)}
            failed = [(pid, r) for pid, r in exits.items()
                      if r is not None and r != 0]
            if failed or all(r is not None for r in exits.values()):
                break
            _time.sleep(0.2)
        else:
            failed = [(pid, -1) for pid, proc in enumerate(procs)
                      if proc.poll() is None]
        if failed:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
            raise RuntimeError(f"local launch failed: {failed}")


def get_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        "dinov3-tpu launcher",
        description="Submit a training script to Slurm (or run locally).",
    )
    parser.add_argument("module_path", type=str,
                        help="Python file containing the callable to launch")
    parser.add_argument("--callable-name", type=str, default="main")
    parser.add_argument("--nodes", type=int, default=1)
    parser.add_argument("--tasks-per-node", type=int, default=1)
    parser.add_argument("--cpus-per-task", type=int, default=8)
    parser.add_argument("--timeout", type=int, default=2800,
                        help="job time limit, minutes")
    parser.add_argument("--slurm-partition", type=str, default=None)
    parser.add_argument("--slurm-account", type=str, default=None)
    parser.add_argument("--slurm-qos", type=str, default=None)
    parser.add_argument("--slurm-nice", type=int, default=0)
    parser.add_argument("--comment", type=str, default="")
    parser.add_argument("--exclude", type=str, default="")
    parser.add_argument("--output-dir", type=str, required=True)
    parser.add_argument("--local", type=int, default=0, metavar="N",
                        help="run locally with N coordinated processes "
                             "instead of submitting to Slurm")
    return parser


def main(argv: Optional[Sequence[str]] = None) -> None:
    logging.basicConfig(level=logging.INFO)
    args, script_args = get_parser().parse_known_args(argv)
    if not os.path.exists(args.module_path):
        raise FileNotFoundError(args.module_path)
    if args.local:
        LocalLauncher(args.local).launch(
            args.module_path, script_args, callable_name=args.callable_name
        )
        return
    script = build_sbatch_script(
        module_path=args.module_path,
        script_args=script_args,
        output_dir=args.output_dir,
        nodes=args.nodes,
        tasks_per_node=args.tasks_per_node,
        cpus_per_task=args.cpus_per_task,
        timeout_min=args.timeout,
        partition=args.slurm_partition,
        account=args.slurm_account,
        qos=args.slurm_qos,
        nice=args.slurm_nice,
        comment=args.comment,
        exclude=args.exclude,
        callable_name=args.callable_name,
    )
    submit_job(script, args.output_dir)


if __name__ == "__main__":
    main()
