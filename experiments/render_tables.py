"""Render EXPERIMENTS.md placeholder tables from artifacts.

Usage: python experiments/render_tables.py   (from the repo root)
Replaces ROOFLINE_TABLE_PLACEHOLDER and PERF_TABLE_PLACEHOLDER in
EXPERIMENTS.md with tables generated from experiments/roofline/*.json and
experiments/perf_log.json."""

import glob
import json
import os

ORDER_A = ["mistral-large-123b", "gemma3-1b", "deepseek-coder-33b", "yi-6b",
           "qwen3-moe-30b-a3b", "moonlight-16b-a3b", "zamba2-2.7b",
           "mamba2-1.3b", "whisper-base", "chameleon-34b"]
ORDER_S = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def roofline_table() -> str:
    rows = [json.load(open(p)) for p in glob.glob("experiments/roofline/*.json")]
    rows.sort(key=lambda c: (ORDER_A.index(c["arch"]), ORDER_S.index(c["shape"])))
    out = ["| arch | shape | compute_s | memory_s | collective_s | dominant "
           "| MODEL_FLOPS | useful | roofline_frac | lever |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for c in rows:
        lever = c["lever"].split(";")[0][:60]
        out.append(
            f"| {c['arch']} | {c['shape']} | {c['compute_s']:.2e} "
            f"| {c['memory_s']:.2e} | {c['collective_s']:.2e} "
            f"| {c['dominant']} | {c['model_flops']:.2e} "
            f"| {c['useful_ratio']:.2f} | {c['roofline_fraction']:.3f} "
            f"| {lever} |")
    return "\n".join(out)


def perf_table() -> str:
    if not os.path.exists("experiments/perf_log.json"):
        return "(perf log missing)"
    logs = json.load(open("experiments/perf_log.json"))
    base = {}
    for l in logs:
        if l["experiment"].endswith("0_baseline"):
            base[(l["arch"], l["shape"])] = l
    out = ["| exp | cell | compute_s | memory_s | collective_s | temp GB "
           "| Δdominant vs baseline | verdict |",
           "|---|---|---|---|---|---|---|---|"]
    for l in sorted(logs, key=lambda x: x["experiment"]):
        b = base.get((l["arch"], l["shape"]))
        dom = b["dominant"] if b else l["dominant"]
        key = f"{dom}_s"
        delta = ""
        verdict = "baseline"
        if b and l is not b and b[key] > 0:
            d = (l[key] / b[key] - 1) * 100
            delta = f"{d:+.1f}% {dom}"
            improved = d < -5
            mem_blowup = l["temp_bytes"] > max(1.5 * b["temp_bytes"], 16e9)
            verdict = ("refuted(mem)" if improved and mem_blowup
                       else "confirmed" if improved
                       else "refuted")
        out.append(
            f"| {l['experiment']} | {l['arch']}×{l['shape']} "
            f"| {l['compute_s']:.2e} | {l['memory_s']:.2e} "
            f"| {l['collective_s']:.2e} | {l['temp_bytes']/1e9:.1f} "
            f"| {delta} | {verdict} |")
    return "\n".join(out)


def main():
    text = open("EXPERIMENTS.md").read()
    text = text.replace("ROOFLINE_TABLE_PLACEHOLDER", roofline_table())
    text = text.replace("PERF_TABLE_PLACEHOLDER", perf_table())
    open("EXPERIMENTS.md", "w").write(text)
    print("tables rendered")


if __name__ == "__main__":
    main()
