"""The plain reference of the ``rls_fleet`` kind against buckets worked by
hand, and the shapes the configuration's numbers give: thresholds, node
shares, the traffic's two request shapes, the worker's reading of an answer."""

import ast
import json
import os

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench.deployments import rls_fleet as kind
from perfbench.generators import open_loop_requests as gen
from perfbench.reference.plain_bucket import OK, OVER_LIMIT, PlainBuckets

CELL = "rls-mesh-4096.paced"
CONFIG = M.config("rls-mesh-4096")


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(M.ROOT, M.HERE, "reference", "plain_bucket.py")
    tree = ast.parse(open(path).read())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    names |= {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert names == {"__future__", "typing", "numpy"}


def test_a_bucket_lapses_ten_buckets_after_it_was_filled():
    ref = PlainBuckets([3])
    assert [ref.hit(1000, 0) for _ in range(4)] == [True, True, True, False]  # bucket 10
    assert ref.live(1999, 0) == 3 and not ref.hit(1999, 0)  # bucket 19: bucket 10 still live
    assert ref.live(2000, 0) == 0  # bucket 20: it has lapsed
    assert ref.hit(2000, 0) and ref.live(2000, 0) == 1
    # the ring slot of bucket 10 was taken over by bucket 20, not added to
    assert ref.live(2999, 0) == 1 and ref.live(3000, 0) == 0


def test_hits_spread_over_buckets_lapse_one_bucket_at_a_time():
    ref = PlainBuckets([4])
    assert all(ref.hit(t, 0) for t in (0, 100, 250, 990))  # buckets 0, 1, 2, 9
    assert not ref.hit(999, 0)
    assert ref.live(1000, 0) == 3 and ref.hit(1000, 0) and not ref.hit(1050, 0)
    assert ref.live(1100, 0) == 3  # bucket 1 gone too, bucket 10 counted


def test_a_hit_of_more_than_one_unit_is_all_or_nothing():
    ref = PlainBuckets([5])
    assert ref.hit(0, 0, 3) and not ref.hit(10, 0, 3)  # 3 + 3 > 5: nothing charged
    assert ref.live(10, 0) == 3
    assert ref.hit(20, 0, 2) and not ref.hit(30, 0, 1)


def test_two_descriptors_are_each_decided_and_charged_though_one_is_refused():
    ref = PlainBuckets([1, 2])
    assert ref.request(0, [0, 1]) == (OK, [True, True])
    # descriptor 0 is spent: the answer is OVER_LIMIT, and descriptor 1 is charged all the same
    assert ref.request(50, [0, 1]) == (OVER_LIMIT, [False, True])
    assert ref.live(50, 1) == 2 and ref.live(50, 0) == 1
    assert ref.request(60, [1]) == (OVER_LIMIT, [False])


def test_descriptors_do_not_share_a_ledger():
    ref = PlainBuckets([1, 1, 1])
    assert [ref.hit(0, d) for d in (0, 1, 2, 1)] == [True, True, True, False]


def test_the_configurations_counts_are_its_threshold_rule_at_the_cells_literal_rate():
    params = M.traffic(M.cell(M.load(), CELL))
    rate = params[params["rate_key"]]
    assert type(rate) in (int, float) and CONFIG["rules"]["sized_for_requests_per_s"] == rate
    counts = np.asarray(CONFIG["rules"]["counts"])
    assert counts.shape == (64, 64) and counts.min() >= 1
    assert (counts == kind.sized_counts(CONFIG, rate)).all()
    # some of the mean offered hits lie over a threshold: at the cell's rate
    # nearly every count rounds up to 1, so the means leave a sixteenth over,
    # and Poisson arrivals against windows of a second about a fifth
    offered = kind.expected_hits_per_s(CONFIG, rate)
    over = np.maximum(offered - counts, 0).sum() / offered.sum()
    assert 0.03 < over < 0.16, over
    assert offered.sum() == pytest.approx(1.25 * rate)


def test_the_configuration_states_what_the_issue_states():
    assert CONFIG["reduced"] == [] and len(CONFIG["source"]) <= 200
    assert CONFIG["fleet"]["lease_slack"] == 0 and CONFIG["fleet"]["shards"] == 4
    assert CONFIG["window"] == {"sample_count": 10, "window_ms": 100}
    assert CONFIG["nodes"]["n"] == 4096 and CONFIG["nodes"]["hits_addend"] == 1
    assert len(CONFIG["guarantees"]) == 4 and CONFIG["assumed"] and CONFIG["size_note"]


def test_every_node_has_a_domain_and_the_largest_holds_a_fifth():
    domains = kind.node_domains(CONFIG)
    held = np.bincount(domains, minlength=64)
    assert len(domains) == 4096 and held.min() >= 1 and (np.diff(held) <= 0).all()
    assert 0.20 < held[0] / 4096 < 0.22


def test_the_traffic_has_both_request_shapes_and_a_second_descriptor_differs():
    dep = kind.build(CONFIG, 1)
    node, desc = gen.traffic(dep, np.random.default_rng(5), 20000)
    two = desc[:, 1] >= 0
    assert 0.23 < two.mean() < 0.27
    assert (desc[two, 0] != desc[two, 1]).all()
    assert (desc[two, 0] // 64 == desc[two, 1] // 64).all()  # of the same domain
    assert (desc[:, 0] // 64 == dep.node_domain[node]).all()  # the node's own
    index, raws = gen.payloads_of(dep, desc)
    assert len(index) == 20000 and len(set(raws)) == len(raws)


def test_a_worker_reads_an_answer_as_the_protobuf_classes_do():
    from sentinel_tpu.rls import rls_pb2 as pb

    rsp = pb.RateLimitResponse(overall_code=OVER_LIMIT)
    for code, left in ((OK, 300), (OVER_LIMIT, 0)):
        status = rsp.statuses.add()
        status.code, status.limit_remaining = code, left
    assert gen.parse_response(rsp.SerializeToString()) == (OVER_LIMIT, [OK, OVER_LIMIT])
    assert gen.parse_response(pb.RateLimitResponse(overall_code=OK).SerializeToString()) == (OK, [])
    assert gen.parse_response(b"") == (0, [])
    # and a request is what the door's own classes read back
    dep = kind.build(CONFIG, 1)
    req = pb.RateLimitRequest.FromString(dep.request_bytes(3, [0, 5]))
    assert req.domain == "mesh-03" and req.hits_addend == 1
    assert [(e.key, e.value) for d in req.descriptors for e in d.entries] == [
        ("destination_cluster", "svc-00"), ("destination_cluster", "svc-05")]


def test_the_new_cells_metric_files_name_readers_that_are_there():
    manifest = M.load()
    names = [m["name"] for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert names == ["gen_late_p99_ms.mesh", "door_ms.mesh", "shard_rpc_ms.mesh", "col_queue_ms.mesh",
                     "col_call_ms.mesh", "col_read_ms.mesh", "col_entries_per_call.mesh",
                     "device_col_ms.mesh"]
    assert M.problems(manifest) == []
    assert json.dumps(M.metric("col_read_ms.mesh")["args"], sort_keys=True) == json.dumps(
        {"attr": "read_ns", "scale": 1e-6, "span": "token.col"}, sort_keys=True)
