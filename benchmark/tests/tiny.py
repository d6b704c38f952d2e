"""The tiny sizes the drivers' CPU tests run at: a cell as the manifest
gives it, its widths and traffic shrunk in memory (no file is changed)."""
from benchmark import harness as H

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=256, sliding_window=4096,
                  max_position_embeddings=4096)


def serve_cell(name: str) -> H.Cell:
    cell = H.Cell(name)
    cell.config.update(TINY_MODEL)
    cell.config["engine"] = dict(max_seqs=4, max_seq_len=256, page_size=16,
                                 prefill_chunk=32, decode_block_size=4,
                                 kv_reserve="on_demand")
    cell.config["model_overrides"].update(dtype="float32",
                                          param_dtype="float32")
    cell.config["correct"].update(prompt_tokens=[70, 6], new_tokens=8,
                                  logit_margin=1e-3)
    cell.traffic["prompt_tokens"] = {"dist": "uniform", "min": 8, "max": 100}
    cell.traffic["output_tokens"] = {"dist": "uniform", "min": 2, "max": 24}
    cell.traffic.update(warmup_s=1.0, drain_s=20.0)
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 3.0
    return cell


def train_cell(name: str, chips: int) -> H.Cell:
    cell = H.Cell(name)
    cell.chips = chips
    cell.config.update(TINY_MODEL)
    cell.config["train"].update(seq_len=64, micro_batch_per_chip=2)
    cell.config["train"]["ds_config"]["optimizer"]["params"]["lr"] = 1e-2
    # 47 positions of a 64-wide bf16 model: the mean's noise is ten times that
    # of 511 positions at the real widths; this test is of the control flow
    cell.config["correct"].update(tokens=48, loss_rel_tol=5e-3)
    cell.traffic["document_tokens"].update(median=20, min=4, max=64)
    return cell
