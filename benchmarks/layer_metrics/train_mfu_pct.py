"""Model FLOP/s utilisation of the LoRA step: the operations a token
requires (``ops.lora_train_flops_per_token``: 4N over the frozen base
plus attention, recomputation not counted) times the tokens a second of
this run's measured steps, over the chips' bf16 peak."""
from benchmarks import ops

NAME, UNIT, BETTER = "train_mfu_pct", "%", "higher"
LAYER = "trainer"
MOVES = "train_tok_s"
SOURCE = "host_clock"
RUNNERS = ("train",)


def compute(run):
    train = run.get("train") or {}
    if not train.get("window_s") or not run.get("peaks"):
        return None
    tok_s = train["steps"] * train["tokens_per_step"] / train["window_s"]
    need = ops.lora_train_flops_per_token(
        run["config"], int(run["config"]["train"]["seq_len"]))
    peak = run["peaks"]["bf16_flops_per_s"] * train["device"]["count"]
    return 100.0 * need * tok_s / peak
