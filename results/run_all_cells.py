"""Driver: run every (arch x shape) dry-run cell sequentially as
subprocesses (fresh device state each), with per-arch microbatches,
merging results into one JSON.

Before launching cells it runs a PUD-backend preflight: a tiny
`repro.sweep` campaign (MAJX + Multi-RowCopy grids, ideal contexts) of
the configured execution backend (PUD_BACKEND env or --pud-backend,
default "pallas"), whose per-point records must all show success 1.0
against the oracle reference — so a bad backend choice fails in seconds
rather than after hours of compiles."""
import json, os, subprocess, sys, tempfile, time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pud_preflight(backend_name: str) -> None:
    sys.path.insert(0, os.path.join(REPO, "src"))
    import shutil
    from repro.sweep import presets, run_sweep

    # A fresh store each invocation: a cached preflight checks nothing.
    root = tempfile.mkdtemp(prefix="pud_preflight_")
    try:
        for spec in presets.preflight_specs(backend_name):
            result = run_sweep(spec, root)
            bad = [r for r in result.records if r["success"] < 1.0]
            assert not bad, (
                f"backend '{backend_name}' lost parity vs oracle on "
                f"{spec.op} points: "
                f"{[(r['x'], r['n_act'], r['success']) for r in bad]}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(f"[preflight] backend '{backend_name}' sweep parity vs oracle OK",
          flush=True)
ARCHS = ["mixtral-8x22b", "qwen3-moe-235b-a22b", "chatglm3-6b", "gemma-7b",
         "deepseek-coder-33b", "glm4-9b", "zamba2-1.2b", "musicgen-medium",
         "xlstm-125m", "phi-3-vision-4.2b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
MB = {"mixtral-8x22b": 8, "qwen3-moe-235b-a22b": 8}

def main():
    multipod = "--multipod" in sys.argv
    skip_cost = "--skip-cost" in sys.argv
    backend = os.environ.get("PUD_BACKEND", "pallas")
    args = sys.argv[1:]
    if "--pud-backend" in args:
        i = args.index("--pud-backend")
        if i + 1 >= len(args):
            sys.exit("--pud-backend requires a backend name")
        backend = args[i + 1]
        del args[i:i + 2]
    # out_path: first non-flag argument, wherever the flags sit —
    # validated *before* the preflight so usage errors fail instantly.
    out_path = next((a for a in args if not a.startswith("--")), None)
    if out_path is None:
        sys.exit("usage: run_all_cells.py OUT_JSON [--pud-backend NAME] "
                 "[--multipod] [--skip-cost] [--serve-rules]")
    pud_preflight(backend)
    results = []
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    done = {(r["arch"], r["shape"]) for r in results if r.get("status") in ("ok", "skipped")}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # Dry-run cells are CPU lowering tools; the preflight above may hold
    # the chip, and a child must never compete with it.
    env["JAX_PLATFORMS"] = "cpu"
    for arch in ARCHS:
        for shape in SHAPES:
            if (arch, shape) in done:
                continue
            cell_out = f"/tmp/cell_{arch}_{shape}.json"
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape,
                   "--microbatches", str(MB.get(arch, 4)),
                   "--out", cell_out]
            if multipod:
                cmd.append("--multipod")
            if skip_cost:
                cmd.append("--skip-cost")
            if "--serve-rules" in sys.argv:
                cmd.append("--serve-rules")
            if os.environ.get("ONLY_KINDS"):
                from_kind = {"train_4k": "train", "prefill_32k": "prefill",
                             "decode_32k": "decode", "long_500k": "decode"}
                if from_kind[shape] not in os.environ["ONLY_KINDS"]:
                    continue
            t0 = time.time()
            p = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                               text=True, timeout=2400)
            try:
                res = json.load(open(cell_out))
                results.extend(res)
                r = res[0]
                status = r.get("status")
                extra = ""
                if status == "ok":
                    extra = (f"mem={r['memory']['total_gb']:.1f}GB "
                             f"bound={r['roofline']['bottleneck']}")
                print(f"[{arch} {shape}] {status} {extra} "
                      f"({time.time()-t0:.0f}s)", flush=True)
            except Exception as e:
                print(f"[{arch} {shape}] FAILED rc={p.returncode}: "
                      f"{p.stderr[-400:]}", flush=True)
                results.append({"arch": arch, "shape": shape,
                                "status": "error", "error": p.stderr[-500:]})
            json.dump(results, open(out_path, "w"), indent=1)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_skip = sum(1 for r in results if r.get("status") == "skipped")
    print(f"TOTAL: {n_ok} ok, {n_skip} skipped, "
          f"{len(results)-n_ok-n_skip} failed")

if __name__ == "__main__":
    main()
