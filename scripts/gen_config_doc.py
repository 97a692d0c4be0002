"""Generate docs/CONFIG.md from the config key registry (single source of
truth: tony_tpu/config/keys.py). Re-run after adding keys, or run with
``--check`` (CI / tier-1) to exit nonzero when docs/CONFIG.md is stale."""

import inspect
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tony_tpu.config import keys as K  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "docs", "CONFIG.md")


def build() -> str:
    src = inspect.getsource(K.Keys)
    lines = ["# Configuration reference", "",
             "Generated from `tony_tpu/config/keys.py` by "
             "`scripts/gen_config_doc.py` — do not edit by hand.",
             "",
             "Layering (low to high precedence): baked defaults → TOML file "
             "→ `-D key=value` CLI overrides → `TONY_CONF_section__key` env.",
             "", "| key | default | notes |", "|---|---|---|"]
    comment = []
    for raw in src.splitlines():
        line = raw.strip()
        if line.startswith("#"):
            text = line.lstrip("# ")
            if not text.startswith("---"):  # skip section markers
                comment.append(text)
            continue
        m = re.match(r'([A-Z0-9_]+) = "([^"]+)"(?:\s*#\s*(.*))?', line)
        if not m:
            if not line:
                comment = []
            continue
        attr, key, inline = m.groups()
        default = K.DEFAULTS.get(key, "—")
        if default == "":
            default = '""'
        note = (inline or " ".join(comment)).replace("|", "\\|")
        comment = []
        lines.append(f"| `{key}` | `{default}` | {note} |")
    lines += ["",
              "## Per-jobtype keys (`job.<type>.*`)", "",
              "| suffix | meaning |", "|---|---|"]
    suffix_doc = {
        "instances": "container count for this task type",
        "memory_mb": "per-container memory ask",
        "cpus": "per-container vcores",
        "tpu_chips": "per-container TPU chips (the yarn.io/gpu analogue)",
        "command": "the user process to exec",
        "env": "extra env (`[\"K=V\", ...]` or table)",
        "depends_on": "launch gating on another task type",
        "depends_timeout_s": "dependency wait budget",
        "untracked": "excluded from job status (e.g. tensorboard)",
        "node_label": "placement constraint (RemoteBackend host labels)",
    }
    for s in K.JOB_SUFFIXES:
        lines.append(f"| `{s}` | {suffix_doc.get(s, '')} |")
    lines += _data_config_section()
    lines += _fit_config_section()
    lines += _serve_config_section()
    return "\n".join(lines) + "\n"


def main(argv: list[str]) -> int:
    content = build()
    if "--check" in argv:
        try:
            with open(OUT) as f:
                current = f.read()
        except FileNotFoundError:
            current = ""
        if current != content:
            print(
                f"{os.path.abspath(OUT)} is stale — rerun "
                "scripts/gen_config_doc.py",
                file=sys.stderr,
            )
            return 1
        print(f"{os.path.abspath(OUT)} is up to date")
        return 0
    with open(OUT, "w") as f:
        f.write(content)
    print(f"wrote {os.path.abspath(OUT)} ({content.count(chr(10))} lines)")
    return 0


def _data_config_section() -> list[str]:
    """Document fit()'s input-pipeline knobs (`data.*` on DataConfig): they
    are Python-API fields set in the training script, not job-file keys,
    but belong in the same reference."""
    import dataclasses

    from tony_tpu.train.data import DataConfig

    notes = {
        "global_batch": "global batch size, divided evenly across processes",
        "seq_len": "tokens per sequence (targets are inputs shifted by one)",
        "vocab_size": "synthetic-stream vocabulary (Zipf marginals)",
        "seed": "synthetic-stream seed; generation is keyed per (seed, step) "
                "so checkpoint resume continues the stream exactly",
        "path": "flat binary int32 token file; empty selects the synthetic "
                "stream",
        "native": "route token files through the C++ prefetching loader "
                  "(shuffled epochs) when it can build; False pins the "
                  "numpy mmap path",
        "prefetch": "device-prefetch depth: batches N+1..N+depth are "
                    "host-generated and device-placed on a background "
                    "thread while the device runs step N; 0 pins the "
                    "synchronous legacy path. Stream order and loss "
                    "trajectory are identical either way (docs/PERF.md "
                    "\"Overlap\")",
    }
    lines = ["", "## Training data (`DataConfig`, Python API)", "",
             "Set on `FitConfig.data` in the training script (e.g. "
             "`DataConfig(prefetch=4)`); these are not job-file keys.", "",
             "| field | default | notes |", "|---|---|---|"]
    for f in dataclasses.fields(DataConfig):
        default = f.default
        default = '""' if default == "" else f"{default}"
        lines.append(
            f"| `data.{f.name}` | `{default}` | "
            f"{notes.get(f.name, '').replace('|', chr(92) + '|')} |"
        )
    return lines


def _fit_config_section() -> list[str]:
    """Document fit()'s trainer knobs (`FitConfig`, scalar fields only —
    `model`/`data`/`rules`/`mesh_shape` are structured Python values with
    their own references)."""
    import dataclasses

    from tony_tpu.train.loop import FitConfig

    notes = {
        "steps": "optimizer steps to run",
        "log_every": "metrics log/push cadence (the first step always logs)",
        "checkpoint_dir": "orbax checkpoint root; empty disables checkpoints",
        "checkpoint_every": "save cadence in steps (0 = only the final save)",
        "checkpoint_keep": "checkpoints retained (older ones pruned)",
        "lr": "peak learning rate (warmup-cosine schedule)",
        "warmup_steps": "linear warmup steps to peak lr",
        "pp_microbatches": "pipeline microbatches when mesh_shape.pp > 1 "
                           "(0 -> 2 per stage)",
        "pp_schedule": "gpipe (autodiff bwd, O(M) activations) \\| 1f1b "
                       "(interleaved bwd, O(P) activations)",
        "resume": "restore from checkpoint_dir when a checkpoint exists",
        "compile_ahead": "AOT-compile the train step on a worker thread "
                         "during startup (docs/PERF.md \"Overlap\")",
        "mu_dtype": "Adam first-moment dtype (float32 \\| bfloat16); bf16 "
                    "frees 2 bytes/param of HBM",
        "ce_impl": "loss-head override: empty keeps model.ce_impl; scan / "
                   "pallas select the fused chunked CE (no [B,S,V] logits "
                   "transient — docs/PERF.md \"Fused cross-entropy\"), "
                   "dense the legacy full-logits head. Chunk/tile sizes: "
                   "`LlamaConfig.ce_vocab_chunk` / `ce_block_n` / "
                   "`ce_block_v`",
        "moe_dispatch": "MoE dispatch override: empty keeps "
                        "model.moe_dispatch; grouped selects the dropless "
                        "sorted grouped GEMM (no capacity slots, no dropped "
                        "tokens — docs/PERF.md \"Grouped MoE\"), gather / "
                        "einsum the fixed-capacity paths. Kernel choice: "
                        "`LlamaConfig.moe_gmm_impl` (scan \\| pallas)",
        "overlap_impl": "comm/compute overlap override: empty keeps "
                        "model.overlap_impl; scan / pallas stream the fsdp "
                        "weight all-gathers through the decomposed "
                        "ppermute-ring matmuls instead of blocking up "
                        "front (`tony_tpu.ops.overlap` — docs/PERF.md "
                        "\"Overlap (collectives)\")",
        "grad_bucket_mb": "dp gradient-reduction bucket size in MiB (0 "
                          "keeps GSPMD's single fused all-reduce); > 0 "
                          "switches to the manual-dp bucketed path — one "
                          "collective per ~bucket of grad leaves, each "
                          "dispatching as its layers' backward completes. "
                          "Size from the measured anatomy report: "
                          "`ops.overlap.bucket_bytes_from_report`. Needs "
                          "dp > 1, pp == 1",
        "moe_group_block": "grouped-GEMM row tile override (0 keeps "
                           "`model.moe_group_block`); each expert's ragged "
                           "token group pads up to a multiple of this",
        "moe_overlap_impl": "overlapped expert-parallel combine override: "
                            "empty keeps `model.moe_overlap_impl`; scan / "
                            "pallas decompose the post-FFN ep psum into "
                            "per-token-chunk partial combines that overlap "
                            "the next chunk's grouped FFN "
                            "(`tony_tpu.ops.moe_overlap` — docs/PERF.md "
                            "\"Round 20\"). Needs ep > 1 and grouped "
                            "dispatch; declines cleanly otherwise",
        "moe_overlap_chunk": "tokens per combine chunk (0 sizes from the "
                             "measured anatomy report via "
                             "`ops.moe_overlap.chunk_tokens_from_report`, "
                             "or auto-picks a divisor); must divide the "
                             "per-shard token count and leave >= 2 chunks, "
                             "else the single-psum path is kept",
        "elastic_members": "elastic gang size at full strength (0 disables; "
                           ">= 2 makes the mesh runtime-swappable — dp maps "
                           "to members and shrinks/grows at generation "
                           "boundaries, docs/ELASTIC.md). In-job this arms "
                           "from the TONY_ELASTIC* env",
        "elastic_dir": "generation-broadcast + journal root; empty uses "
                       "TONY_APP_DIR (the shared app dir the AM writes "
                       "generation.json into)",
        "elastic_shadow_steps": "async device->host checkpoint-shadow "
                                "stride in steps (0 -> env/default 16); "
                                "each shadow briefly holds one extra state "
                                "replica on device",
    }
    # structured Python values with their own references (elastic_plan is
    # the scripted {step: members} membership plan bench/tests drive)
    skip = {"model", "data", "rules", "mesh_shape", "on_metrics",
            "elastic_plan"}
    lines = ["", "## Trainer (`FitConfig`, Python API)", "",
             "Set on `fit(FitConfig(...))` in the training script; these are "
             "not job-file keys. `model` (LlamaConfig), `data` (DataConfig "
             "above), `mesh_shape` (MeshShape) and `rules` carry the "
             "structured configs.", "",
             "| field | default | notes |", "|---|---|---|"]
    for f in dataclasses.fields(FitConfig):
        if f.name in skip:
            continue
        default = f.default
        default = '""' if default == "" else f"{default}"
        lines.append(f"| `{f.name}` | `{default}` | {notes.get(f.name, '')} |")
    return lines


def _serve_config_section() -> list[str]:
    """Document the decode engine's knobs (`ServeConfig`, Python API —
    docs/SERVE.md has the architecture and sizing guidance)."""
    import dataclasses

    from tony_tpu.serve.engine import ServeConfig

    notes = {
        "slots": "concurrent decode slots (the static batch width of the "
                 "one jitted decode step); a finished request frees its "
                 "slot for the admission queue",
        "max_len": "longest prompt+generation admitted (0 -> "
                   "model.max_seq_len)",
        "kv_block": "KV cache block size: capacity grows/shrinks in "
                    "multiples of this and the decode kernel tiles the "
                    "sequence by it (docs/SERVE.md)",
        "prefill_buckets": "prompt pad lengths — prefill compiles once per "
                           "bucket (bounded compile count); () -> powers "
                           "of two from 16 up to max_len",
        "decode_impl": "form of the int8 weight matmul (quant.weights): "
                       "scan (pure XLA, default) \\| pallas (fused kernel) "
                       "— tony_tpu.ops.quant_mm; the paged decode attention "
                       "does not read it (its kernel on a TPU, the scan "
                       "elsewhere)",
        "max_top_k": "static top-k slice width for sampling; per-request "
                     "top_k clamps to it, and top-p-only requests use it "
                     "as the bounded nucleus candidate set",
        "shrink": "release cache blocks when the live maximum drops to "
                  "half the capacity (each capacity change recompiles the "
                  "decode step once)",
    }
    lines = ["", "## Serving (`ServeConfig`, Python API)", "",
             "Set on `Engine(params, cfg, ServeConfig(...))` "
             "(tony_tpu.serve); `generate()` builds one internally. These "
             "are not job-file keys.", "",
             "| field | default | notes |", "|---|---|---|"]
    for f in dataclasses.fields(ServeConfig):
        default = f.default
        default = '""' if default == "" else f"{default}"
        lines.append(f"| `{f.name}` | `{default}` | {notes.get(f.name, '')} |")
    return lines


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
