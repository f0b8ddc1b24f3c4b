"""Tiny stand-ins for a cell's configuration and traffic, for runs of
the harness on the CPU."""
import copy
import json
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def tiny_config(name="deepseek-llm-7b"):
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    c.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=4, head_dim=32, intermediate_size=256,
             vocab_size=512)
    c["engine"] = dict(c["engine"], pool_blocks=256, block_size=16,
                       decode_bucket_b=4, seq_bucket=512)
    c["scheduler"] = dict(c["scheduler"], max_decode_batch=4)
    c["store"] = dict(hbm_bytes=1 << 24, cpu_bytes=1 << 24, n_chunks=32,
                      m_variants=2)
    c["compile"] = {"prefill_bucket": 128}
    return c


def tiny_mix(name="rag_cold"):
    m = copy.deepcopy(json.loads(
        (HERE / "traffic" / f"{name}.json").read_text()))
    m["kb"].update(chunks=min(m["kb"]["chunks"], 48), len_median=32,
                   len_min=16, len_max=64, len_quantum=16)
    m["retrieval"].update(sessions=min(m["retrieval"]["sessions"], 4))
    m.update(system_len=8, question_len=8, lead_in_s=0.5,
             warmup_requests=4, check_requests=3)
    m["output"].update(median=4, min=2, max=6)
    if m["arrival"]["kind"] == "poisson":
        m["arrival"]["rate_per_s"] = 2.0
    else:
        m["arrival"].update(clients=3, max_requests=200)
    return m


def tiny_reuse_mix():
    """An open loop over a shared knowledge base: Zipf chunk popularity
    and sessions that share retrieval sets, so that requests hit chunk
    caches made under other prefixes."""
    m = tiny_mix("rag_cold")
    m["kb"].update(chunks=48, fresh=False)
    m["retrieval"].update(sessions=4, queries_per_session=3)
    m["arrival"] = {"kind": "poisson", "rate_per_s": 2.0}
    return m
