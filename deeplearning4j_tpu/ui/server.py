"""Training-visualization web server.

Parity with the reference `deeplearning4j-ui/.../UiServer.java:70` (Dropwizard
app + per-view REST resources: weights histograms, activations, flow/model
graph, score). Stdlib http.server (no web-framework dependency); listeners
POST JSON snapshots exactly like the reference's JAX-RS client
(HistogramIterationListener.java:51,206 POST /weights/update?sid=...).

Endpoints:
  POST /weights/update?sid=S   body: {"score":..,"parameters":{..},"gradients":{..}}
  GET  /weights/data?sid=S     full history for a session
  GET  /weights/latest?sid=S
  POST /flow/update?sid=S      model-topology JSON (FlowIterationListener analog)
  GET  /flow/data?sid=S
  GET  /sessions
  GET  /                       minimal self-contained dashboard (score chart)
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from .storage import HistoryStorage, SessionStorage

_DASHBOARD = """<!DOCTYPE html>
<html><head><title>dl4j-tpu training UI</title></head>
<body style="font-family:sans-serif">
<h2>dl4j-tpu training UI</h2>
<p><a href="/weights">weights</a> | <a href="/activations">activations</a> |
<a href="/filters">filters</a> |
<a href="/flow">flow</a> | <a href="/tsne">t-SNE view</a> |
<a href="/nearestneighbors">nearest neighbors</a> |
<a href="/serving">serving</a></p>
<div id="sessions"></div>
<canvas id="chart" width="900" height="320" style="border:1px solid #ccc"></canvas>
<script>
async function refresh() {
  const sessions = await (await fetch('/sessions')).json();
  document.getElementById('sessions').innerText = 'sessions: ' + sessions.join(', ');
  if (!sessions.length) return;
  const data = await (await fetch('/weights/data?sid=' + sessions[0])).json();
  const scores = data.map(d => d.score);
  const c = document.getElementById('chart').getContext('2d');
  c.clearRect(0, 0, 900, 320);
  if (!scores.length) return;
  const max = Math.max(...scores), min = Math.min(...scores);
  c.beginPath();
  scores.forEach((s, i) => {
    const x = 20 + i * (860 / Math.max(scores.length - 1, 1));
    const y = 300 - 280 * (s - min) / Math.max(max - min, 1e-9);
    i ? c.lineTo(x, y) : c.moveTo(x, y);
  });
  c.strokeStyle = '#0074D9'; c.stroke();
  c.fillText('score: ' + scores[scores.length-1].toFixed(5), 25, 15);
}
setInterval(refresh, 2000); refresh();
</script></body></html>"""


_WEIGHTS_PAGE = """<!DOCTYPE html>
<html><head><title>weights</title></head><body style="font-family:sans-serif">
<h2>Weights view</h2>
<p>score chart + per-parameter histograms + mean-magnitude time series
(HistogramIterationListener view)</p>
<canvas id="score" width="900" height="220" style="border:1px solid #ccc"></canvas>
<h3>Mean magnitudes</h3>
<canvas id="mags" width="900" height="220" style="border:1px solid #ccc"></canvas>
<div id="legend" style="font-size:11px"></div>
<h3>Parameter histograms (latest iteration)</h3>
<div id="hists"></div>
<script>
const COLORS = ['#0074D9','#FF4136','#2ECC40','#FF851B','#B10DC9','#39CCCC',
                '#85144b','#3D9970','#111111','#AAAAAA'];
function line(ctx, xs, W, H, color, mn, mx) {
  if (!xs.length) return;
  if (mx === undefined) { mx = Math.max(...xs); mn = Math.min(...xs); }
  ctx.beginPath();
  xs.forEach((v,i) => {
    const x = 20 + i*(W-40)/Math.max(xs.length-1,1);
    const y = H-20 - (H-40)*(v-mn)/Math.max(mx-mn,1e-9);
    i ? ctx.lineTo(x,y) : ctx.moveTo(x,y);
  });
  ctx.strokeStyle = color; ctx.stroke();
}
async function refresh() {
  const sid = new URLSearchParams(location.search).get('sid') || 'default';
  // slim series for the charts; full histograms only for the LATEST entry
  const data = await (await fetch('/weights/series?sid=' + sid)).json();
  if (!data.length) return;
  const sc = document.getElementById('score').getContext('2d');
  sc.clearRect(0,0,900,220);
  line(sc, data.map(d=>d.score), 900, 220, '#0074D9');
  sc.fillText('score: ' + data[data.length-1].score.toFixed(5), 25, 12);
  const mg = document.getElementById('mags').getContext('2d');
  mg.clearRect(0,0,900,220);
  const names = Object.keys(data[data.length-1].mean_magnitudes || {});
  // ONE shared scale so series are comparable (vanishing vs exploding)
  const series = names.map(n => data.map(d=>(d.mean_magnitudes||{})[n]||0));
  const gmx = Math.max(...series.flat(), 1e-9);
  const gmn = Math.min(...series.flat());
  names.forEach((n,i) =>
    line(mg, series[i], 900, 220, COLORS[i % COLORS.length], gmn, gmx));
  mg.fillText('scale: ' + gmn.toPrecision(3) + ' .. ' + gmx.toPrecision(3),
              25, 12);
  document.getElementById('legend').innerHTML = names.map((n,i) =>
    '<span style="color:' + COLORS[i%COLORS.length] + '">&#9632; ' + n +
    '</span>').join(' ');
  const hs = document.getElementById('hists');
  hs.innerHTML = '';
  const latest = await (await fetch('/weights/latest?sid=' + sid)).json();
  const params = (latest || {}).parameters || {};
  for (const [name, h] of Object.entries(params)) {
    const div = document.createElement('div');
    div.style.cssText = 'display:inline-block;margin:4px';
    div.innerHTML = '<div style="font-size:11px">' + name + '</div>' +
      '<canvas width="220" height="120" style="border:1px solid #eee"></canvas>';
    hs.appendChild(div);
    const c = div.querySelector('canvas').getContext('2d');
    const mx = Math.max(...h.counts, 1);
    h.counts.forEach((v,i) => {
      const bw = 200/h.counts.length;
      c.fillStyle = '#0074D9';
      c.fillRect(10 + i*bw, 110 - 100*v/mx, bw-1, 100*v/mx);
    });
  }
}
setInterval(refresh, 3000); refresh();
</script></body></html>"""

_ACTIVATIONS_PAGE = """<!DOCTYPE html>
<html><head><title>activations</title></head>
<body style="font-family:sans-serif">
<h2>Convolutional activations</h2>
<p>first-example channel heatmaps per conv layer
(ConvolutionalIterationListener view)</p>
<div id="layers"></div>
<script>
async function refresh() {
  const sid = new URLSearchParams(location.search).get('sid') || 'default';
  const d = await (await fetch('/activations/data?sid=' + sid)).json();
  if (!d || !d.layers) return;
  const root = document.getElementById('layers');
  root.innerHTML = '<p>iteration ' + d.iteration + ', score ' +
                   (d.score||0).toFixed(5) + '</p>';
  d.layers.forEach(L => {
    const h = document.createElement('h3');
    h.innerText = 'layer ' + L.layer + ' (' + L.h + 'x' + L.w + ')';
    root.appendChild(h);
    L.channels.forEach(grid => {
      const cv = document.createElement('canvas');
      const scale = Math.max(1, Math.floor(64 / L.h));
      cv.width = L.w*scale; cv.height = L.h*scale;
      cv.style.cssText = 'margin:2px;border:1px solid #ddd';
      root.appendChild(cv);
      const ctx = cv.getContext('2d');
      grid.forEach((row,y) => row.forEach((v,x) => {
        const g = Math.round(255*v);
        ctx.fillStyle = 'rgb(' + g + ',' + g + ',' + g + ')';
        ctx.fillRect(x*scale, y*scale, scale, scale);
      }));
    });
  });
}
setInterval(refresh, 5000); refresh();
</script></body></html>"""

_FILTERS_PAGE = """<!DOCTYPE html>
<html><head><title>filters</title></head>
<body style="font-family:sans-serif">
<h2>Convolution filters</h2>
<p>learned kernels per conv layer, input-channel mean, normalized per
filter (FilterIterationListener view)</p>
<div id="layers"></div>
<script>
async function refresh() {
  const sid = new URLSearchParams(location.search).get('sid') || 'default';
  const d = await (await fetch('/filters/data?sid=' + sid)).json();
  if (!d || !d.layers) return;
  const root = document.getElementById('layers');
  root.innerHTML = '<p>iteration ' + d.iteration + ', score ' +
                   (d.score||0).toFixed(5) + '</p>';
  d.layers.forEach(L => {
    const h = document.createElement('h3');
    const shown = (L.shown && L.shown < L.n_out)
      ? ' (showing ' + L.shown + ' of ' + L.n_out + ')' : '';
    h.innerText = 'layer ' + L.layer + ': ' + L.n_out + ' filters ' +
                  L.kh + 'x' + L.kw + 'x' + L.n_in + shown;
    root.appendChild(h);
    L.filters.forEach(grid => {
      const cv = document.createElement('canvas');
      const scale = Math.max(4, Math.floor(48 / L.kh));
      cv.width = L.kw*scale; cv.height = L.kh*scale;
      cv.style.cssText = 'margin:2px;border:1px solid #ddd';
      root.appendChild(cv);
      const ctx = cv.getContext('2d');
      grid.forEach((row,y) => row.forEach((v,x) => {
        const g = Math.round(255*v);
        ctx.fillStyle = 'rgb(' + g + ',' + g + ',' + g + ')';
        ctx.fillRect(x*scale, y*scale, scale, scale);
      }));
    });
  });
}
setInterval(refresh, 5000); refresh();
</script></body></html>"""

_FLOW_PAGE = """<!DOCTYPE html>
<html><head><title>flow</title></head><body style="font-family:sans-serif">
<h2>Model flow</h2>
<p>layer graph (FlowIterationListener view)</p>
<canvas id="c" width="960" height="640" style="border:1px solid #ccc"></canvas>
<script>
async function draw() {
  const sid = new URLSearchParams(location.search).get('sid') || 'default';
  const m = await (await fetch('/flow/data?sid=' + sid)).json();
  if (!m || !m.layers) return;
  const ctx = document.getElementById('c').getContext('2d');
  ctx.clearRect(0,0,960,640); ctx.font = '11px sans-serif';
  const pos = {input: [480, 30]};
  const W = 150, H = 34;
  m.layers.forEach((L,i) => {
    // simple layered placement: depth = longest input chain
    let depth = 1 + Math.max(0, ...L.inputs.map(s =>
        pos[s] ? Math.round((pos[s][1]-30)/60) : 0));
    const row = m.layers.filter((o,j) => j < i &&
        Math.round((pos[o.name][1]-30)/60) === depth).length;
    pos[L.name] = [120 + row*320 + (depth%2)*40, 30 + depth*60];
  });
  ctx.fillStyle = '#eee';
  ctx.fillRect(pos.input[0]-W/2, pos.input[1]-H/2, W, H);
  ctx.strokeRect(pos.input[0]-W/2, pos.input[1]-H/2, W, H);
  ctx.fillStyle = '#111'; ctx.fillText('input', pos.input[0]-14, pos.input[1]+3);
  m.layers.forEach(L => {
    const [x,y] = pos[L.name];
    L.inputs.forEach(src => {
      const p = pos[src]; if (!p) return;
      ctx.beginPath(); ctx.moveTo(p[0], p[1]+H/2);
      ctx.lineTo(x, y-H/2); ctx.strokeStyle = '#888'; ctx.stroke();
    });
    ctx.fillStyle = '#d0e4ff';
    ctx.fillRect(x-W/2, y-H/2, W, H);
    ctx.strokeStyle = '#555'; ctx.strokeRect(x-W/2, y-H/2, W, H);
    ctx.fillStyle = '#111';
    ctx.fillText(L.name + ': ' + L.type, x-W/2+6, y-3);
    if (L.n_params !== undefined)
      ctx.fillText(L.n_params + ' params', x-W/2+6, y+11);
  });
}
draw(); setInterval(draw, 5000);
</script></body></html>"""

_TSNE_PAGE = """<!DOCTYPE html>
<html><head><title>t-SNE</title></head><body style="font-family:sans-serif">
<h2>t-SNE embedding</h2>
<canvas id="c" width="800" height="600" style="border:1px solid #ccc"></canvas>
<script>
async function draw() {
  const d = await (await fetch('/tsne/data' + location.search)).json();
  if (!d.coords || !d.coords.length) return;
  const xs = d.coords.map(p=>p[0]), ys = d.coords.map(p=>p[1]);
  const minx=Math.min(...xs), maxx=Math.max(...xs);
  const miny=Math.min(...ys), maxy=Math.max(...ys);
  const c = document.getElementById('c').getContext('2d');
  c.clearRect(0,0,800,600); c.font = '10px sans-serif';
  d.coords.forEach((p,i) => {
    const x = 20 + 760*(p[0]-minx)/Math.max(maxx-minx,1e-9);
    const y = 20 + 560*(p[1]-miny)/Math.max(maxy-miny,1e-9);
    c.fillStyle = '#0074D9'; c.fillRect(x-1,y-1,3,3);
    if (d.labels && d.labels[i]) { c.fillStyle='#333'; c.fillText(d.labels[i], x+3, y); }
  });
}
draw(); setInterval(draw, 5000);
</script></body></html>"""

_SERVING_PAGE = """<!DOCTYPE html>
<html><head><title>Serving metrics</title></head>
<body style="font-family:sans-serif">
<h2>Serving SLO metrics</h2>
<div id="meta"></div>
<div id="decode" style="color:#555"></div>
<div id="mesh" style="color:#555"></div>
<div id="kvpool" style="color:#555"></div>
<div id="kvtier" style="color:#555"></div>
<div id="robust" style="color:#555"></div>
<div id="slo" style="color:#555"></div>
<div id="fleet" style="color:#555"></div>
<div id="trace" style="font-family:monospace;font-size:12px"></div>
<table id="t" border="1" cellpadding="4" style="border-collapse:collapse">
</table>
<script>
function esc(s) {
  // request ids can be CLIENT-SUPPLIED (X-Request-Id is honored), so
  // they must never reach innerHTML unescaped
  return String(s).replace(/[&<>"']/g, c => ({'&': '&amp;', '<': '&lt;',
    '>': '&gt;', '"': '&quot;', "'": '&#39;'}[c]));
}
function waterfall(r) {
  // one summary line per recent request: phase widths proportional to
  // the request's share of the slowest request shown
  const phases = [['queue_ms', '#bbb'], ['restore_ms', '#9c6'],
                  ['prefill_ms', '#69c'], ['decode_ms', '#c96']];
  const total = r.total_ms || 0.001;
  let bars = '';
  for (const [k, col] of phases) {
    const w = Math.round(260 * (r[k] || 0) / waterfall.max);
    if (w > 0) bars += '<span style="display:inline-block;height:10px;' +
      'width:' + w + 'px;background:' + col + '" title="' + k + '=' +
      (+r[k] || 0) + 'ms"></span>';
  }
  return '<div>' + esc(r.request_id) + ' ' + (r.outcome === 'cancel' ?
    'CANCELLED' : (+r.tokens || 0) + ' tok') +
    (r.retries ? ' <b title="survived ' + (+r.retries) +
      ' engine restart(s)">&#10227;' + (+r.retries) + '</b>' : '') +
    ' ' + total.toFixed(1) +
    'ms ' + bars + ' <span style="color:#888">queue ' +
    (+r.queue_ms || 0) + ' | restore ' + (+r.restore_ms || 0) +
    ' | prefill ' + (+r.prefill_ms || 0) + ' | decode ' +
    (+r.decode_ms || 0) + '</span></div>';
}
async function refresh() {
  const d = await (await fetch('/serving/data' + location.search)).json();
  const m = d.metrics || {};
  document.getElementById('meta').innerText =
    'uptime: ' + (m.uptime_sec || 0) + 's';
  const tr = d.trace || [];
  waterfall.max = Math.max(0.001, ...tr.map(r => r.total_ms || 0));
  document.getElementById('trace').innerHTML = tr.length ?
    '<p><b>recent requests</b> (queue&#9632;restore&#9632;prefill' +
    '&#9632;decode)</p>' + tr.map(waterfall).join('') : '';
  const c = m.counters || {}, h = m.histograms || {};
  const r = m.ratios || {};
  const ttft = h.generate_first_token_seconds, ck = h.prefill_chunk_size;
  const lk = c.prefix_cache_lookup_tokens_total;
  if (c.prefill_tokens_total !== undefined || ttft)
    document.getElementById('decode').innerText =
      'decode: ' + (c.decode_tokens_total || 0) + ' tokens, ' +
      (c.prefill_tokens_total || 0) + ' prefilled' +
      (ck && ck.count ? ' (chunk p50 ' + ck.p50 + ')' : '') +
      (ttft && ttft.count ? ', TTFT p50 ' +
        (ttft.p50 * 1000).toFixed(1) + 'ms' : '') +
      (lk !== undefined ? ', prefix hit ' +
        (100 * (r.prefix_cache_hit_rate || 0)).toFixed(1) + '% of ' +
        lk + ' looked-up tokens' +
        (c.prefix_cache_evicted_blocks_total ? ' (' +
          c.prefix_cache_evicted_blocks_total + ' blocks evicted)' : '')
        : '') +
      (c.spec_tokens_proposed_total !== undefined ?  // speculative decode
        ', spec accept ' +
        (100 * (r.spec_acceptance_rate || 0)).toFixed(1) + '% of ' +
        c.spec_tokens_proposed_total + ' drafted' : '') +
      (c.decode_forks_total ? ', ' + c.decode_forks_total +
        ' best-of-n forks' : '') +
      (c.decode_cancelled_total ? ', ' + c.decode_cancelled_total +
        ' cancelled' : '');
  const g = m.gauges || {};
  if (g.decode_mesh_devices)  // tensor-parallel mesh topology line
    document.getElementById('mesh').innerText =
      'mesh: tensor-parallel over ' + g.decode_mesh_devices.value +
      ' devices (tp axis, KV pool head-sharded)' +
      (g.kv_pool_device_bytes ? ', ' +
        ((g.kv_pool_device_used_bytes || {}).value || 0) + ' / ' +
        g.kv_pool_device_bytes.value + ' KV bytes per device' : '');
  if (g.kv_pool_blocks_capacity)  // paged KV pool occupancy line
    document.getElementById('kvpool').innerText =
      'kv pool: ' + (g.kv_pool_blocks_live ?
        g.kv_pool_blocks_live.value : 0) + ' live / ' +
      (g.kv_pool_blocks_free ? g.kv_pool_blocks_free.value : 0) +
      ' free of ' + g.kv_pool_blocks_capacity.value + ' blocks (' +
      (100 * (r.kv_pool_utilization || 0)).toFixed(1) + '% used' +
      ', peak ' + (g.kv_pool_blocks_live ?
        g.kv_pool_blocks_live.max : 0) + ')' +
      (c.decode_preempted_total ? ', ' + c.decode_preempted_total +
        ' preempted' : '');
  // hierarchical KV tiering line (inference/kvtier.py): host/disk
  // occupancy, per-tier hit rates over directory lookups, spill and
  // promote traffic — "is the spill ladder earning its budget"
  if (g.kv_tier_host_bytes !== undefined)
    document.getElementById('kvtier').innerText =
      'kv tiers: host ' + (g.kv_tier_host_blocks ?
        g.kv_tier_host_blocks.value : 0) + ' blocks (' +
      ((g.kv_tier_host_bytes.value || 0) / 1048576).toFixed(2) + 'MB)' +
      (g.kv_tier_disk_blocks && g.kv_tier_disk_blocks.value ?
        ', disk ' + g.kv_tier_disk_blocks.value + ' blocks (' +
        ((g.kv_tier_disk_bytes || {}).value / 1048576 || 0).toFixed(2) +
        'MB)' : '') +
      ', directory ' + ((g.kv_tier_directory_entries || {}).value || 0) +
      ' entries, hit host ' +
      (100 * (r.kv_tier_host_hit_rate || 0)).toFixed(1) + '%' +
      (r.kv_tier_disk_hit_rate ? ' / disk ' +
        (100 * r.kv_tier_disk_hit_rate).toFixed(1) + '%' : '') +
      ' of ' + (c.kv_tier_lookups_total || 0) + ' lookups, ' +
      (c.kv_tier_spilled_blocks_total || 0) + ' spilled / ' +
      (c.kv_tier_promoted_blocks_total || 0) + ' promoted' +
      (c.kv_tier_restore_failed_total ? ', ' +
        c.kv_tier_restore_failed_total + ' restore failure(s)' : '');
  // fault-tolerance line (inference/supervisor.py): readiness, engine
  // restarts, recovered/abandoned requests, degradation rung, chaos
  // triggers — the at-a-glance "is the supervisor earning its keep"
  if (g.serving_ready !== undefined || c.engine_restarts_total)
    document.getElementById('robust').innerText =
      'robustness: ' + ((g.serving_ready || {}).value ? 'READY'
        : 'NOT READY') +
      ', ' + (c.engine_restarts_total || 0) + ' engine restart(s), ' +
      (c.requests_recovered_total || 0) + ' recovered' +
      (c.requests_abandoned_total ? ', ' + c.requests_abandoned_total +
        ' abandoned (retry budget)' : '') +
      (c.requests_shed_total ? ', ' + c.requests_shed_total +
        ' shed' : '') +
      ', degradation L' + ((g.degradation_level || {}).value || 0) +
      (c.failpoint_triggers_total ? ', ' + c.failpoint_triggers_total +
        ' failpoint trigger(s)' : '');
  // attribution & SLO line (inference/profiler.py): rolling tokens/s
  // and MFU estimate from the cost-attribution plane, plus the latency
  // objective's burn rates — "why is the fleet at 31% MFU" and "is p99
  // burning" at a glance
  const mfu = g.device_mfu_estimate, tps = g.decode_tokens_per_sec;
  const burnF = g.slo_burn_rate_fast, burnS = g.slo_burn_rate_slow;
  if (mfu || tps || g.slo_objective_p99_ms)
    document.getElementById('slo').innerText =
      'attribution: ' + (tps ? tps.value.toFixed(1) + ' tok/s, ' : '') +
      (mfu ? 'MFU ~' + (100 * mfu.value).toFixed(2) + '%, ' : '') +
      (g.device_hbm_gbps ? g.device_hbm_gbps.value.toFixed(3) +
        ' GB/s attributed' : '') +
      (g.slo_objective_p99_ms ? ' | SLO p99<=' +
        g.slo_objective_p99_ms.value + 'ms, burn fast ' +
        (burnF ? burnF.value.toFixed(2) : '0') + 'x / slow ' +
        (burnS ? burnS.value.toFixed(2) : '0') + 'x' : '');
  // fleet line (serving/telemetry.py federation, pushed by the
  // telemetry CLI's --ui flag): replicas up, fleet-level p99 per
  // route from MERGED histogram buckets, traffic-weighted burn rates
  const fl = d.fleet;
  if (fl) {
    const routes = Object.entries(fl.routes || {}).map(([r, v]) =>
      esc(r) + ' p99 ' + v.p99_ms + 'ms').join(', ');
    document.getElementById('fleet').innerHTML =
      'fleet: ' + (+fl.replicas_up || 0) + '/' +
      (+fl.replicas_total || 0) + ' replicas up' +
      (routes ? ' | ' + routes : '') +
      ' | burn fast ' + (+fl.burn_rate_fast || 0).toFixed(2) +
      'x / slow ' + (+fl.burn_rate_slow || 0).toFixed(2) + 'x' +
      (fl.burning ? ' <b style="color:#c00">BURNING</b>' : '') +
      (fl.scrape_errors_total ? ', ' + (+fl.scrape_errors_total) +
        ' scrape error(s)' : '');
  }
  let rows = '<tr><th>metric</th><th>value</th></tr>';
  for (const [k, v] of Object.entries(m.counters || {}))
    rows += '<tr><td>' + k + '</td><td>' + v + '</td></tr>';
  for (const [k, v] of Object.entries(m.gauges || {}))
    rows += '<tr><td>' + k + '</td><td>' + v.value +
            ' (max ' + v.max + ')</td></tr>';
  for (const [k, h] of Object.entries(m.histograms || {}))
    rows += '<tr><td>' + k + '</td><td>n=' + (h.count || 0) +
            (h.count ? ' p50=' + h.p50 + ' p95=' + h.p95 +
                       ' p99=' + h.p99 : '') + '</td></tr>';
  document.getElementById('t').innerHTML = rows;
}
refresh(); setInterval(refresh, 2000);
</script></body></html>"""

_NN_PAGE = """<!DOCTYPE html>
<html><head><title>Nearest neighbors</title></head>
<body style="font-family:sans-serif">
<h2>Nearest neighbors (VPTree)</h2>
<input id="w" placeholder="word"/> <input id="k" value="10" size="3"/>
<button onclick="go()">search</button><ul id="out"></ul>
<script>
async function go() {
  const w = document.getElementById('w').value;
  const k = document.getElementById('k').value;
  const r = await (await fetch('/nearestneighbors/search?word=' +
      encodeURIComponent(w) + '&k=' + k + (location.search ?
      '&' + location.search.slice(1) : ''))).json();
  document.getElementById('out').innerHTML =
    (r.neighbors||[]).map(n => '<li>' + n.label + ' (' +
                          n.distance.toFixed(4) + ')</li>').join('');
}
</script></body></html>"""


class UiServer:
    """Reference UiServer (singleton getInstance() pattern).

    Round-3 adds the reference's remaining per-view REST resources
    (deeplearning4j-ui/.../tsne/ and nearestneighbors/): uploaded t-SNE
    coordinates render as a scatter page, and uploaded word vectors are
    VPTree-indexed (reference nearestneighbors resource is vptree-backed)
    for interactive nearest-label search."""

    _instance: Optional["UiServer"] = None

    def __init__(self, port: int = 0):
        self.history = HistoryStorage()
        self.flow = SessionStorage()
        self.tsne = SessionStorage()
        self.activations = SessionStorage()
        self.filters = SessionStorage()
        self.serving = SessionStorage()
        self._nn_trees = {}
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _json(self, obj, code=200):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _html(self, text):
                body = text.encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/html")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                url = urlparse(self.path)
                q = parse_qs(url.query)
                sid = q.get("sid", ["default"])[0]
                if url.path == "/":
                    return self._html(_DASHBOARD)
                if url.path == "/sessions":
                    return self._json(server.history.sessions())
                if url.path == "/weights":
                    return self._html(_WEIGHTS_PAGE)
                if url.path == "/weights/data":
                    return self._json(server.history.get(sid))
                if url.path == "/weights/series":
                    # chart-sized slice of the history: score + magnitudes
                    # only (the full per-iteration histograms are multi-MB
                    # on long runs and the page reads just the latest)
                    return self._json([
                        {"iteration": d.get("iteration"),
                         "score": d.get("score"),
                         "mean_magnitudes": d.get("mean_magnitudes", {})}
                        for d in server.history.get(sid)])
                if url.path == "/weights/latest":
                    return self._json(server.history.latest(sid))
                if url.path == "/activations":
                    return self._html(_ACTIVATIONS_PAGE)
                if url.path == "/activations/data":
                    return self._json(server.activations.get(sid, "latest")
                                      or {})
                if url.path == "/filters":
                    return self._html(_FILTERS_PAGE)
                if url.path == "/filters/data":
                    return self._json(server.filters.get(sid, "latest")
                                      or {})
                if url.path == "/flow":
                    return self._html(_FLOW_PAGE)
                if url.path == "/flow/data":
                    return self._json(server.flow.get(sid, "model"))
                if url.path == "/tsne":
                    return self._html(_TSNE_PAGE)
                if url.path == "/tsne/data":
                    return self._json(server.tsne.get(sid, "coords")
                                      or {"coords": [], "labels": []})
                if url.path == "/serving":
                    return self._html(_SERVING_PAGE)
                if url.path == "/serving/data":
                    return self._json(server.serving.get(sid, "latest")
                                      or {})
                if url.path == "/nearestneighbors":
                    return self._html(_NN_PAGE)
                if url.path == "/nearestneighbors/search":
                    word = q.get("word", [""])[0]
                    try:
                        k = int(q.get("k", ["10"])[0])
                    except ValueError:
                        return self._json({"error": "k must be an integer"},
                                          400)
                    return self._json(server._nn_search(sid, word, k))
                return self._json({"error": "not found"}, 404)

            def do_POST(self):
                url = urlparse(self.path)
                q = parse_qs(url.query)
                sid = q.get("sid", ["default"])[0]
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
                if url.path == "/weights/update":
                    server.history.put(sid, payload)
                    return self._json({"status": "ok"})
                if url.path == "/flow/update":
                    server.flow.put(sid, "model", payload)
                    return self._json({"status": "ok"})
                if url.path == "/activations/update":
                    server.activations.put(sid, "latest", payload)
                    return self._json({"status": "ok"})
                if url.path == "/filters/update":
                    server.filters.put(sid, "latest", payload)
                    return self._json({"status": "ok"})
                if url.path == "/tsne/update":
                    server.tsne.put(sid, "coords",
                                    {"coords": payload.get("coords", []),
                                     "labels": payload.get("labels", [])})
                    return self._json({"status": "ok"})
                if url.path == "/serving/update":
                    # MERGE top-level keys (atomically, inside the
                    # storage lock): the engine-side pusher owns
                    # "metrics"/"trace", the fleet telemetry CLI owns
                    # "fleet" — two independent pushers composing one
                    # page must not clobber each other's keys (a pusher
                    # re-sending a key it owns still replaces it)
                    server.serving.merge(sid, "latest", payload)
                    return self._json({"status": "ok"})
                if url.path == "/nearestneighbors/update":
                    server._nn_index(sid, payload.get("labels", []),
                                     payload.get("vectors", []))
                    return self._json({"status": "ok"})
                return self._json({"error": "not found"}, 404)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    # -- nearest-neighbors view backend (VPTree, reference
    # deeplearning4j-ui/.../nearestneighbors resource) -------------------------
    def _nn_index(self, sid: str, labels, vectors) -> None:
        import numpy as np
        from ..clustering.trees import VPTree
        arr = np.asarray(vectors, dtype=float)
        self._nn_trees[sid] = (VPTree(arr, labels=list(labels)),
                               {w: i for i, w in enumerate(labels)}, arr)

    def _nn_search(self, sid: str, word: str, k: int) -> dict:
        entry = self._nn_trees.get(sid)
        if entry is None:
            return {"error": "no index uploaded for session"}
        tree, word_to_idx, arr = entry
        if word not in word_to_idx:
            return {"error": f"unknown word {word!r}"}
        idxs, dists = tree.search(arr[word_to_idx[word]], k + 1)
        out = [{"label": tree.labels[i], "distance": float(d)}
               for i, d in zip(idxs, dists) if tree.labels[i] != word][:k]
        return {"word": word, "neighbors": out}

    @classmethod
    def get_instance(cls, port: int = 0) -> "UiServer":
        if cls._instance is None:
            cls._instance = UiServer(port)
        return cls._instance

    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if UiServer._instance is self:
            UiServer._instance = None
