"""chip_smoke.py off the chip: it must FAIL without a TPU, and its whole
control flow must be rehearsable on the CPU — by a switch of THIS TEST
(monkeypatching the platform check and the size table), never by an
option of the program. Also the rules the smoke rests on: the compile
cache helper, the accelerator requirement of bench.py / the trainer,
and the single-process distributed bootstrap."""

import importlib.util
import json
import os

import jax
import pytest

from test_lm_decoder import TINY as LM_TINY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# vit_test width, kernels interpreted: the CPU rehearsal of every phase
TINY = {
    "train_overrides": [
        "data.backend=synthetic", "train.batch_size_per_device=2",
        "student.arch=vit_test", "crops.global_crops_size=32",
        "crops.local_crops_size=16", "crops.local_crops_number=2",
        "dino.head_n_prototypes=256", "dino.head_hidden_dim=64",
        "ibot.head_n_prototypes=256", "ibot.head_hidden_dim=64"],
    "train_iters": 3,
    # two layers, one of each kind of mixer and of FFN (KDA + dense, MLA +
    # routed): the rehearsal is of the smoke's control flow, and the
    # decoder's step compiles twice in it (fresh, resumed)
    "lm_overrides": ["data.backend=synthetic", *LM_TINY,
                     "lm.num_hidden_layers=2", "lm.kda_layers=[1]",
                     "lm.full_attn_layers=[2]"],
    "lm_iters": 3,
    "lm_timeout_s": 600,
    "mesh_global_batch": 8,
    "mesh_iters": 2,
    "flash_shape": (1, 200, 2, 32),
    "ln_shape": (64, 128),
    "kda_shape": (1, 128, 1, 128),
    "kernel_interpret": True,
    # lanes of 128 and blocks that are multiples of it: what the kernels
    # (interpreted here) take; a window no block divides
    "gqa_shapes": {"window": (1, 512, 6, 2, 128, 128, 200),
                   "global": (1, 512, 6, 2, 128, 128, None)},
    "gdn_shape": (1, 128, 1, 2, 128),
    "gdn_attn_shapes": {"gated": (1, 512, 4, 2, 256, 256, None)},
    "dsa_shape": (1, 256, 2, 1, 128, 2, 16, 32),
    "sconv_shape": (1, 256, 128),
    "sconv_attn_shapes": {"heads64": (1, 256, 8, 2, 64, 64, None)},
    "moe_shapes": {"tiny": (256, 4, 512, 4, 128, 128, "silu", (0.3,))},
    "mla_shape": (1, 256, 2, 192, 64),
    "mla_blocks": (128, 256),
    "mla_latent_shapes": {"mla8k": (2, 256, 2, None, True),
                          "mla16k": (1, 256, 2, 1e6, False)},
    "mla_attn_shapes": {"wide16k": (1, 256, 1, 1, 256, 128, None)},
    "ssd_shape": (1, 256, 2, 64, 1, 128),
    "ssd_chunks": (256,),
    "ssd_chain_blocks": ((64, 128),),
    "ssd_attn_shapes": {"gqa16": (1, 256, 16, 1, 128, 128, None)},
    "ssd_moe_shapes": {"tiny15": (256, 4, 512, 4, 128, 192, "relu2", (0.3,))},
    "gqa_shipped_blocks": (128, 256),
    "gqa_blocks": [(256, 128)],
    "gqa_timeout_s": 600,
    "serve_overrides": [
        "student.arch=vit_test", "student.patch_size=4", "serve.min_px=8",
        "serve.max_px=32", "serve.rows=4", "serve.row_tokens=65",
        "serve.max_segments_per_row=12", "train.scan_layers=true"],
    "serve_images_hw": [(8, 8), (16, 16), (32, 32), (12, 20), (16, 16)],
}


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    mod = _load("chip_smoke")
    monkeypatch.setattr(mod, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(mod, "RUN_DIR", str(tmp_path / "run"))
    return mod


def _rehearse(smoke, monkeypatch, count):
    """The test's rehearsal switch: pretend the platform check passed
    (reporting ``count`` devices) and shrink the size table."""
    monkeypatch.setattr(smoke, "SIZES", TINY)
    monkeypatch.setattr(smoke, "require_tpu", lambda: {
        "platform": "tpu", "kind": jax.devices()[0].device_kind,
        "count": count})


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_smoke_fails_without_a_tpu(smoke, capsys, argv):
    """On the CPU backend the script exits non-zero before any phase
    and prints no result line."""
    with pytest.raises(SystemExit) as exc:
        smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_refuses_a_device_count_it_was_not_asked_for(
        smoke, monkeypatch, capsys):
    _rehearse(smoke, monkeypatch, count=4)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])  # one chip asked, four reported
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_rehearsal_runs_every_phase(smoke, monkeypatch, capsys):
    """trainer (self-check, train, save, resume), the step under
    accumulation, kernels, serve — and the last stdout line is exactly
    the contract's object."""
    _rehearse(smoke, monkeypatch, count=1)
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": jax.devices()[0].device_kind,
        "count": 1}}
    said = "\n".join(ln for ln in lines if ln.startswith("[chip_smoke"))
    for needle in ("self-check", "0 failures", "resumed at 3",
                   "step[accum2]: ibot_rows_fill", "kernels: flash ", "kernels: flash_seg",
                   "kernels: fused_layernorm", "kernels: kda_chunk_fwd",
                   "decay spikes of 200", "serve:",
                   "compiles packed 1", "lm: losses", "lm: resumed at 3",
                   "gqa: window core (1, 512, 6, 2, 128, 128) window 200: the "
                   "entry point takes the kernel (interpreted)",
                   "gqa: global core, tiles: first calls",
                   "gqa: global core, kernel at blocks 256 x 128",
                   "gdn: delta rule (1, 128, 1, 2, 128) at a scalar gate: the "
                   "entry point takes the kernel (scalar gate, interpreted)",
                   "gdn: delta rule: norm of the difference over the norm",
                   "gqa: gated core (1, 512, 4, 2, 256, 256) window None: the "
                   "entry point takes the kernel (interpreted)",
                   "dsa: selected plane:", "0 queries miscounted",
                   "dsa: core (1, 256, 2, 1, 128) under a selection: the entry "
                   "point takes the kernel (interpreted)",
                   "dsa: core forward, with its rows' log-sum-exp",
                   "dsa: the index loss with its gradient",
                   "dsa: index loss: kernel to strips",
                   "dsa: core under the causal triangle at 256 tokens",
                   "dsa: core: norm of the difference over the norm, kernel, "
                   "plane in program to whole rows",
                   "sconv: chain (1, 256, 128): the entry point takes the "
                   "kernel (interpreted)",
                   "sconv: chain: norm of the difference over the norm",
                   "gqa: heads64 core (1, 256, 8, 2, 64, 64) window None: the "
                   "entry point takes the kernel (interpreted)",
                   "moe_rows: tiny N 256 K 4 cap 512 D 128 fill 0.3: 2.0 pairs a "
                   "buffer row, the layer's combine takes the sorted",
                   "moe_rows: tiny fill 0.3, sorted: dispatch",
                   "moe_rows: tiny fill 0.3: every form within 1e-2 of the "
                   "parent's by norm, all four",
                   "moe: tiny (512, 4, 128, 128) silu: the layer takes the "
                   "kernel (interpreted), 256 rows a visit",
                   "moe: tiny fill 0.3: norm of the difference over the norm",
                   "largest value past the last group 0.0e+00",
                   "mla: turn (1, 256, 2, 192) + (1, 256, 1, 64): first calls",
                   "mla: turn: norm of the difference over the norm, to the "
                   "complex multiplication",
                   "mla: mla8k latent core (2, 256, 2, 128, 64, 128) theta None: "
                   "the mixer takes the kernel (interpreted)",
                   "mla: mla8k latent core, tiles: first calls",
                   "mla: mla16k latent core (1, 256, 2, 128, 64, 128) theta "
                   "1000000.0: the mixer takes the kernel (interpreted)",
                   "mla: mla16k latent core: norm of the difference over the "
                   "norm, output and gradients q kvb kpe: kernel to the dense "
                   "masked softmax",
                   "gqa: wide16k core (1, 256, 1, 1, 256, 128) window None: the "
                   "entry point takes the kernel (interpreted)",
                   "ssd: scan (1, 256, 2, 64, 1, 128): the entry point takes "
                   "the kernel (interpreted)",
                   "ssd: scan: norm of the difference over the norm, kernel "
                   "to plain scan, output and gradients u B C dt a",
                   "ssd: scan, kernel at chunks of 256: forward",
                   "ssd: chains (1, 256) x 128 | 384 | 2 in a plane of 640: "
                   "the mixer takes the kernel (interpreted)",
                   "ssd: chain conv: norm of the difference over the norm, "
                   "kernel to plain chain, output and gradients plane taps "
                   "bias",
                   "ssd: chain norm: norm of the difference over the norm, "
                   "kernel to plain chain, output and gradients y xbc plane D "
                   "scale",
                   "ssd: chain norm, kernel at blocks of 64 x 128: first call",
                   "gqa: gqa16 core (1, 256, 16, 1, 128, 128) window None: the "
                   "entry point takes the kernel (interpreted)",
                   "moe: tiny15 (512, 4, 128, 192) relu2: the layer takes the "
                   "kernel (interpreted), 256 rows a visit",
                   "all phases passed"):
        assert needle in said, needle
    assert "mesh[" not in said  # the four-chip phase is behind --chips 4


def test_smoke_runs_the_phases_it_is_asked_for(smoke, monkeypatch, capsys):
    """--phases dsa: one phase alone (as the first chip call of a new
    program asks for it; the cheapest here: the rehearsal above has run
    every phase once, the decoder's trainer took 220 s of this suite a
    second time), and a phase nobody knows is refused."""
    _rehearse(smoke, monkeypatch, count=1)
    assert smoke.main(["--phases", "dsa"]) == 0
    said = capsys.readouterr().out
    assert "dsa: selected plane:" in said and "all phases passed" in said
    assert "trainer:" not in said and "lm:" not in said
    with pytest.raises(SystemExit, match="unknown phases"):
        smoke.main(["--phases", "lm,nope"])


def test_smoke_wraps_no_phase_in_an_except():
    """The first failing phase ends the run."""
    import ast

    tree = ast.parse(open(os.path.join(REPO, "chip_smoke.py")).read())
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Try)]


def test_smoke_four_chip_phase_on_virtual_devices(smoke, monkeypatch,
                                                  capsys, eight_devices):
    """--chips 4 runs ONLY the sharded arms and their one-device
    comparison, reports state on four devices, and says count 4."""
    _rehearse(smoke, monkeypatch, count=4)
    assert smoke.main(["--chips", "4"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert json.loads(lines[-1])["device"]["count"] == 4
    said = "\n".join(ln for ln in lines if ln.startswith("[chip_smoke"))
    assert "mesh[one_device]" in said
    assert "mesh[dp]: mesh {'data': 4} zero3=False bucketed=True" in said
    assert "mesh[fsdp]: mesh {'fsdp': 4} zero3=True" in said
    assert said.count("state leaves on [4] devices") == 2
    assert "zero3_stream" in said  # the collectives the text holds
    for absent in ("trainer:", "kernels:", "serve:"):
        assert absent not in said


def test_compile_cache_helper_follows_the_variable(monkeypatch):
    from dinov3_tpu.utils import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: the helper sets NO directory in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        # not set: one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert configure_compile_cache() == fixed  # and it never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_cache_path_outside_the_helper():
    """No entry point names a cache directory of its own."""
    import subprocess

    hits = subprocess.run(
        ["grep", "-rnE", "/tmp/jaxcache|gettempdir|BENCH_CACHE_DIR",
         "bench.py", "chip_smoke.py", "scripts", "dinov3_tpu",
         "tests/conftest.py"],
        cwd=REPO, capture_output=True, text=True).stdout
    assert hits == "", hits


def test_accelerator_is_required_unless_cpu_is_asked_for(monkeypatch):
    """bench.py, the trainer and the evals CLI share this rule: a cpu
    backend is refused unless asked for explicitly."""
    from dinov3_tpu.utils import require_accelerator

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="wanted a 'tpu' backend"):
        require_accelerator("tpu")
    got = require_accelerator("cpu")  # MODEL.DEVICE=cpu: explicit
    assert got["platform"] == "cpu" and got["count"] == len(jax.devices())
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # explicit, by environment
    assert require_accelerator("tpu")["platform"] == "cpu"


def test_bench_is_one_process_and_refuses_the_cpu(monkeypatch):
    bench = _load("bench")
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and "os._exit" not in src
    for gone in ("_supervise", "_tpu_required", "_probe_backend_subprocess",
                 "_maybe_stall_probe", "_run_attempt"):
        assert not hasattr(bench, gone), gone
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="wanted a 'tpu' backend"):
        bench.main()


def test_single_process_run_never_initializes_distributed(monkeypatch):
    """A host that merely LOOKS pod-like starts no multi-host init; one
    that was asked for and fails raises."""
    from dinov3_tpu.parallel import distributed

    calls = []

    def boom(**kw):
        calls.append(kw)
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setattr(distributed.jax.distributed, "initialize", boom)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    distributed.initialize_distributed()
    assert calls == [] and not distributed._initialized
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "0")
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        distributed.initialize_distributed()
    assert calls == [{"coordinator_address": "localhost:1",
                      "num_processes": 2, "process_id": 0}]


def test_native_kernels_say_which_arm_they_are():
    from dinov3_tpu import native

    said = native.describe()
    assert said != "not loaded yet"
    assert ("built" in said) or ("fallback" in said)
    if native.native_available():
        # built inside the checkout, never under the home directory
        assert os.path.join(REPO, ".native_build") in said or \
            os.environ.get("DINOV3_TPU_NATIVE_DIR", "\0") in said


def test_resume_frees_the_restore_template(monkeypatch, tmp_path):
    """What the chip refused first: a resumed ViT-L run held the freshly
    initialised state (the restore's template) next to the restored one
    and its first step ran out of device memory. The template's buffers
    are freed right after the restore."""
    import dinov3_tpu.train.setup as train_setup
    from dinov3_tpu.train.train import main as train_main

    common = ["--config-file",
              os.path.join(REPO, "configs", "train", "vitl16_im1k.yaml"),
              "--output-dir", str(tmp_path / "run"), *TINY["train_overrides"]]
    train_main(["--no-resume", "--max-iterations", "2", *common])

    templates = []
    real_resume = train_setup.elastic_resume

    def spy(setup, ckpt, **kw):
        templates.extend(jax.tree.leaves(setup.state))
        return real_resume(setup, ckpt, **kw)

    monkeypatch.setattr(train_setup, "elastic_resume", spy)
    resumed = train_main(["--max-iterations", "3", *common])
    assert resumed["iterations"] == 3 and len(resumed["losses"]) == 1
    assert templates and all(leaf.is_deleted() for leaf in templates)
