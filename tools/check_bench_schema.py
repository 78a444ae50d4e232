"""BENCH_codec schema gate: schema 9 + `blocks` + prefix/fault/shard rows.

    python tools/check_bench_schema.py BENCH_codec.smoke.json

Run by `make bench-smoke` (and therefore `make check` / CI) right after
the smoke bench writes its artifact, so a codec_json change that drops
the per-row tuned-blocks record, the shared-prefix serving rows, or the
schema itself fails the build instead of silently shipping an
unparseable trajectory artifact. Schema 6 requires the serving section
to carry the shared-prefix comparison: a cache-on row with TTFT fields
and ``prefix_hit_rate > 0`` (the warm tree really served wire pages),
and the matching cache-off baseline row. Schema 7 adds the
``serving_faults`` section: the overload pair must show preemption
actually firing when enabled (``preemptions >= 1`` on, ``== 0`` off)
and the injection row must show containment (``poisoned >= 1`` with
``token_parity`` true — survivors bit-identical to a fault-free run).
Schema 8 adds the ``serving_sharded`` section: tensor-parallel decode
rows at tp in {1, 2, 4, 8}, compressed collectives on and off. The
gates: every compress-on row moves strictly fewer interconnect bytes
than its f32 twin, tp=1 moves zero, and tp=8 device-normalized
throughput is >= tp=1 under both compress settings (the scaling claim
the PR makes). TTFT and goodput *magnitudes* are not gated —
wall-clock comparisons belong in the artifact, not a CI assert.
"""

import json
import sys

KERNEL_SECTIONS = ("qmatmul", "lns_qmatmul", "kv_attention",
                   "kv_attention_paged")
PREFIX_FIELDS = ("ttft_us_mean", "ttft_us_max", "prefix_hit_rate",
                 "prefix_hit_tokens", "shared_prefix_tokens",
                 "tokens_per_s")
OVERLOAD_FIELDS = ("n_requests", "us", "goodput_tokens_per_s",
                   "ttft_us_p50", "ttft_us_p99", "preemptions",
                   "completed", "path")
INJECT_FIELDS = ("n_requests", "us", "fault_rate", "fault_seed",
                 "injected", "poisoned", "unaffected", "token_parity",
                 "quarantined_pages", "path")
SHARDED_FIELDS = ("tp", "compress", "steps", "decode_batch", "us",
                  "tokens_per_s_wall", "tokens_per_s", "normalization",
                  "interconnect_bytes_per_step", "pool_shard_bytes",
                  "path")


def check(path: str) -> None:
    with open(path) as f:
        doc = json.load(f)
    assert doc.get("schema") == 9, \
        f"{path}: schema {doc.get('schema')!r}, expected 9"
    assert doc.get("autotune_mode") in ("0", "1", "force"), \
        f"{path}: missing/invalid autotune_mode"
    n_rows = 0
    for sec in KERNEL_SECTIONS:
        rows = doc.get(sec)
        assert rows, f"{path}: missing kernel section {sec!r}"
        for key, row in rows.items():
            blocks = row.get("blocks")
            assert isinstance(blocks, list) and blocks and \
                all(isinstance(b, int) and b > 0 for b in blocks), \
                f"{path}: {sec}/{key} has no valid blocks ({blocks!r})"
            assert "us" in row and "path" in row, \
                f"{path}: {sec}/{key} missing us/path"
            n_rows += 1
    roof = doc.get("roofline")
    assert roof, f"{path}: missing roofline section"
    for key, pt in roof.items():
        assert pt.get("dominant") in ("compute", "memory"), \
            f"{path}: roofline/{key} missing dominant term"
        assert pt.get("bound_us_v5e") is not None, \
            f"{path}: roofline/{key} missing bound"
    serving = doc.get("serving") or {}
    on_rows = {k: r for k, r in serving.items()
               if k.startswith("prefix/") and k.endswith("/on")}
    off_rows = {k: r for k, r in serving.items()
                if k.startswith("prefix/") and k.endswith("/off")}
    assert on_rows and off_rows, \
        f"{path}: serving is missing the prefix/<fmt>/on|off row pair"
    for key, row in {**on_rows, **off_rows}.items():
        for field in PREFIX_FIELDS:
            assert row.get(field) is not None, \
                f"{path}: serving/{key} missing {field}"
    for key, row in on_rows.items():
        assert row["prefix_hit_rate"] > 0, \
            f"{path}: serving/{key} hit rate 0 — warm tree served nothing"
        assert key.replace("/on", "/off") in off_rows, \
            f"{path}: serving/{key} has no cache-off baseline row"
    faults = doc.get("serving_faults") or {}
    for key in ("overload/preempt_on", "overload/preempt_off",
                "inject/nar"):
        assert key in faults, f"{path}: serving_faults missing {key!r} row"
    for key in ("overload/preempt_on", "overload/preempt_off"):
        for field in OVERLOAD_FIELDS:
            assert faults[key].get(field) is not None, \
                f"{path}: serving_faults/{key} missing {field}"
    assert faults["overload/preempt_on"]["preemptions"] >= 1, \
        f"{path}: preempt_on row saw no preemption — the VIP never evicted"
    assert faults["overload/preempt_off"]["preemptions"] == 0, \
        f"{path}: preempt_off row preempted — the toggle is broken"
    nar = faults["inject/nar"]
    for field in INJECT_FIELDS:
        assert nar.get(field) is not None, \
            f"{path}: serving_faults/inject/nar missing {field}"
    assert nar["poisoned"] >= 1, \
        f"{path}: injection poisoned nobody — NaR detection is dead"
    assert nar["token_parity"] is True, \
        f"{path}: a surviving request diverged — containment is broken"
    assert nar["quarantined_pages"] >= 1, \
        f"{path}: poisoned pages were not quarantined"
    sharded = doc.get("serving_sharded") or {}
    for tp in (1, 2, 4, 8):
        for side in ("on", "off"):
            key = f"tp{tp}/{side}"
            assert key in sharded, \
                f"{path}: serving_sharded missing {key!r} row"
            row = sharded[key]
            for field in SHARDED_FIELDS:
                # "compress" is null by design in the f32 (off) rows
                assert field in row, \
                    f"{path}: serving_sharded/{key} missing {field}"
            assert row["tp"] == tp, f"{path}: {key} tp field mismatch"
    assert sharded["tp1/off"]["interconnect_bytes_per_step"] == 0, \
        f"{path}: tp=1 claims interconnect traffic — census is wrong"
    for tp in (2, 4, 8):
        on = sharded[f"tp{tp}/on"]["interconnect_bytes_per_step"]
        off = sharded[f"tp{tp}/off"]["interconnect_bytes_per_step"]
        assert 0 < on < off, \
            (f"{path}: tp={tp} compressed collectives do not move fewer "
             f"bytes (on={on}, off={off})")
    for side in ("on", "off"):
        t1 = sharded[f"tp1/{side}"]["tokens_per_s"]
        t8 = sharded[f"tp8/{side}"]["tokens_per_s"]
        assert t8 >= t1, \
            (f"{path}: tp=8 normalized throughput {t8} < tp=1 {t1} "
             f"(compress={side}) — sharding does not scale")
    print(f"# {path}: schema 9 ok — {n_rows} kernel rows with blocks, "
          f"{len(roof)} roofline points, {len(on_rows)} prefix serving "
          f"pair(s), hit_rate="
          f"{[r['prefix_hit_rate'] for r in on_rows.values()]}, "
          f"preemptions={faults['overload/preempt_on']['preemptions']}, "
          f"poisoned={nar['poisoned']} (parity ok), sharded tp8/tp1 "
          f"normalized={sharded['tp8/off']['tokens_per_s']}/"
          f"{sharded['tp1/off']['tokens_per_s']} tok/s, compressed "
          f"bytes/step={sharded['tp8/on']['interconnect_bytes_per_step']}"
          f" vs f32 {sharded['tp8/off']['interconnect_bytes_per_step']}")


if __name__ == "__main__":
    for p in sys.argv[1:] or ["BENCH_codec.smoke.json"]:
        check(p)
