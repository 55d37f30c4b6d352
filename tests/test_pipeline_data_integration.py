"""Integration: full train loop (CLI path) with checkpoint resume."""
import os
import subprocess
import sys


def _run(args, timeout=600):
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"   # tests keep no cache
    return subprocess.run([sys.executable, "-m"] + args, env=env, cwd=root,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli_runs_and_learns(tmp_path):
    r = _run(["repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
              "--steps", "12", "--batch", "4", "--seq", "64",
              "--lr", "1e-3", "--ckpt-dir", str(tmp_path),
              "--ckpt-every", "6"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done; final loss" in r.stdout
    # checkpoints written
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


def test_train_cli_resume(tmp_path):
    r1 = _run(["repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
               "--steps", "6", "--batch", "4", "--seq", "64",
               "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = _run(["repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
               "--steps", "9", "--batch", "4", "--seq", "64",
               "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
               "--resume"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 6" in r2.stdout


def test_serve_cli(tmp_path):
    r = _run(["repro.launch.serve", "--arch", "qwen3-0.6b", "--smoke",
              "--requests", "2", "--prompt-len", "4", "--gen", "4",
              "--max-len", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout
