"""Examples smoke tests — every example runs end-to-end in FAST mode
(reference analog: dl4j-examples compiled+run in CI)."""
import os
import runpy
from pathlib import Path

import jax
import pytest

EXAMPLES = Path(__file__).parent.parent / "examples"



@pytest.mark.parametrize("name", [
    "lenet_mnist", "char_rnn_textgen", "bert_finetune",
    "distributed_data_parallel", "samediff_autodiff",
    "parallelism_modes",
    "hyperparameter_search", "transfer_learning",
    "model_serving", "pretrained_zoo",
    "long_context_attention",
    "sharded_serving",
    "causal_lm",
    "bert_pretrain_mlm",
])
def test_example_runs(name, monkeypatch, capsys):
    monkeypatch.setenv("DL4J_TPU_EXAMPLE_FAST", "1")
    runpy.run_path(str(EXAMPLES / f"{name}.py"), run_name="__main__")
    out = capsys.readouterr().out
    assert out.strip(), f"{name} produced no output"
